"""Share of the traced window, in %, in which a chip ran a cross-chip
all-reduce with no compute beside it (averaged over the cell's chips),
in an LLM training cell: the time of the all-reduce operations in the
chips' operation lines (``all-reduce``, or the ``-start`` and ``-done``
halves of an asynchronous one). A chip's operation line runs one
operation at a time, so no compute op runs on that chip during them.
``phases.py`` prints this reader's value beside the phase split."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    t = sum(v for k, v in tr["op_time"].items()
            if k.split(".")[0].startswith("all-reduce"))
    if t <= 0:
        return None
    return 100.0 * t / tr["window_s"]
