"""Median age of a written batch when its results reach the consumer:
arrival of the response that passes the batch's last offset, minus the
time the batch was due to be written. One sample per written batch."""

import statistics


def read(obs):
    if not obs.get("ages_s"):
        return None
    return statistics.median(obs["ages_s"]) * 1000.0
