"""Pages read from the block device per completed request over the window
(``stats`` RPC ``device.read_pages``; page-cache hits are not reads)."""


def read(ctx):
    if ctx["completed"] <= 0:
        return None
    pages = ctx["after"]["device"]["read_pages"] \
        - ctx["before"]["device"]["read_pages"]
    return pages / ctx["completed"]
