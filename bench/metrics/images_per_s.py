"""Images whose logits came back to the host in the window, per second."""


def read(rec):
    c = rec["counts"]
    return c["images"] / c["window_s"] if "images" in c else None
