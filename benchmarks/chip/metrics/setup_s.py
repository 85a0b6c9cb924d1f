"""Set-up seconds: process start to the window's start (weights, engine
build and warm-up, shared prefix, pre-roll)."""


def read(rec):
    return rec["setup_s"]
