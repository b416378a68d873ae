"""Process start to window open: corpus, log write, device open, compile
or cache load, warm-up, reference check."""


def read(obs):
    return obs["setup_s"]
