"""Process start to the first timed frame: weights, frame bank, server
construction, compile or cache load, warm-up.  Host clock."""


def read(rec):
    return rec["setup_s"]
