"""Paged attention kernel: KV pages holding a key a real row attends over
the pages the kernel walks (padded rows x padded table width), in %, summed
over the window's ``nightjar.decode`` spans (their ``pages_valid`` and
``pages_walked``, counted by the program where it builds the tables)."""
from harness.spans import pages


def read(ctx):
    spans = ctx.trace.get("nightjar") if ctx.trace is not None else None
    if not spans:
        return None
    valid, walked = pages(spans)
    return 100.0 * valid / walked if walked else None
