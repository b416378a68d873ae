"""Input records of the backlog whose results reached the consumer, per
second of window: progress in input offsets (each response's
``next_filter_offset``) over the time from window open to the response
boundary that closed it."""


def read(obs):
    return obs["records_in"] / obs["window_s"]
