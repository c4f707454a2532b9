"""Real prompt tokens prefilled over the rows x padded length the window's
prefills computed (``serve/admit`` attributes ``useful_tokens``, ``rows``,
``padded_len``), in percent."""

from chipbench import spanread


def read(run):
    return spanread.attr_share(run, "serve/admit", "useful_tokens", "rows",
                               "padded_len")
