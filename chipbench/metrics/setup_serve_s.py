"""Duration of the ``serve/setup`` span: ``Runtime.serve`` from its entry to
the start of ``engine.run`` (engine construction, warm-up, front-end
set-up), the part of ``setup_s`` after imports and weights."""


def read(run):
    spans = getattr(run, "spans", None)
    setup = [s for s in spans or () if s.name == "serve/setup"]
    return setup[-1].end - setup[-1].start if setup else None
