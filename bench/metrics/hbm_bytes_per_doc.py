"""Device bytes in use at the end of the window over the docs indexed:
what a deployment pays per document in chip memory."""


def read(w):
    return w.bytes_in_use / w.cfg["n_docs"] if w.bytes_in_use else None
