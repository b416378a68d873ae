"""The one percentile rule the benchmark's metrics share."""


def percentile(values, q: float):
    """The sample at rank ``int(q * n)`` of the sorted values (nearest
    rank, no interpolation); None for an empty sample."""
    v = sorted(values)
    if not v:
        return None
    return v[min(len(v) - 1, int(q * len(v)))]
