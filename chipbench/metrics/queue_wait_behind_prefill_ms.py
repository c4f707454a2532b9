"""Mean over the requests due in the window and admitted of the part of
their queue wait (due time to admission stamp) that ``serve/admit`` spans
of other requests cover: waiting behind another group's prefill."""

from chipbench import spanread


def read(run):
    return spanread.wait_behind(run, "serve/admit")
