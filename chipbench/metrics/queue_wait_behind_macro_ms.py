"""Mean over the requests due in the window and admitted of the part of
their queue wait (due time to admission stamp) that ``serve/macro`` spans
cover: waiting behind a decode macro-step."""

from chipbench import spanread


def read(run):
    return spanread.wait_behind(run, "serve/macro")
