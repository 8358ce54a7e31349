"""The host's share of a training step: the self time of the program's
``train/forward``, ``train/backward`` and ``train/step`` spans (what the Python
side took to enqueue the step) over the step's period (from one ``train/forward``
to the next), the median over the window's steps. The window's steps are the
last ones in the program's span ring (``benchmarks/lib/program.py``)."""

from statistics import median

from benchmarks.lib import program

UNIT, BETTER, SOURCE = "%", "lower", "program_span"
LAYER = "trainer step loop (runtime/engine.py)"
MOVES = "train_tokens_per_s"
SPANS = ("train/forward", "train/backward", "train/step")


def read(record):
    n = int((record.get("train") or {}).get("steps") or 0)
    prog = program.of(record) if n >= 2 else None
    forwards = program.last(prog, "train/forward", n)
    if not forwards:
        return None
    own = program.self_times(prog["spans"])
    mine = sorted((s for s in prog["spans"] if s["name"] in SPANS and s["start_s"] >= forwards[0]["start_s"]),
                  key=lambda s: s["start_s"])
    starts = [f["start_s"] for f in forwards]
    host = [0.0] * n
    step = -1
    for s in mine:
        step += s["name"] == "train/forward"
        host[step] += own[s["id"]]
    return 100.0 * median(h / (b - a) for h, a, b in zip(host, starts, starts[1:]))
