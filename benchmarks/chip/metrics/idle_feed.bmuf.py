"""Device idle charged to ``repro.train.stack`` or ``repro.train.update``
(innermost open span at each idle instant, first chip), over the window:
the chips' wait while the Trainer lays a block's batches out and
dispatches its update (bench/spans.py).  A program without the
``train.stack`` span reads the update's share alone."""
from bench.spans import PREFIX, idle_share

OWNERS = (PREFIX + "train.stack", PREFIX + "train.update")


def read(run, res, tr):
    return idle_share(run, res, tr, lambda k: k in OWNERS)
