"""Output tokens delivered inside the window over the window's seconds."""


def read(rec):
    c = rec["client"]
    return c["output_tokens"] / c["window_s"]
