"""Share (%) of the traced span's device-idle time (its gaps of 50 us or
more) under no `fluvio/` annotation of the program: what the tracing
still leaves unexplained. None without the consumer's records (whose
time a program without them leaves unnamed) or this run's own trace."""

from spubench.consume_spans import idle_unnamed_share


def read(obs):
    return idle_unnamed_share(obs)
