"""Tokens the window's macro-steps emitted over the slot-steps they
computed (``serve/macro`` attributes ``emitted``, ``rows``, ``k``), in
percent."""

from chipbench import spanread


def read(run):
    return spanread.attr_share(run, "serve/macro", "emitted", "rows", "k")
