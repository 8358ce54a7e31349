"""The host's share of a serving quantum: the self time of the spans in which
the Python side works on the quantum's critical path (``serve/admit``,
``serve/schedule``, ``fused/validate``, ``fused/operands``, ``fused/program``,
``fused/dispatch``, ``fused/account``, ``serve/commit`` and what
``infer/fused_step`` leaves between them) over the quantum's period (from its
``serve/admit`` to the end of its ``serve/commit``), the median over the
window's quanta. ``fused/readback`` is left out of the host's side: with
``_drive_sla``'s undeferred steps it is where the host waits for the device.
Quanta that held a program's first call are left out altogether."""

from statistics import median

from benchmarks.lib import program

UNIT, BETTER, SOURCE = "%", "lower", "program_span"
LAYER = "engine step host path (inference/v2/engine_v2.py _run_fused, replay.py _drive_sla)"
MOVES = "serve_tokens_per_s"
HOST = ("serve/admit", "serve/schedule", "serve/commit", "infer/fused_step", "fused/validate", "fused/operands",
        "fused/program", "fused/dispatch", "fused/account")


def read(record):
    prog = program.of(record)
    quanta = program.last(prog, "infer/fused_step", len(record.get("quanta") or ()))
    if not quanta:
        return None
    own = program.self_times(prog["spans"])
    # an earlier engine of this process counted its quanta from 0 too: only spans after the last quantum before the window
    before = [s["start_s"] + s["dur_s"] for s in prog["spans"]
              if s["name"] == "infer/fused_step" and s["start_s"] < quanta[0]["start_s"]]
    by_q = {}
    for s in prog["spans"]:
        if "q" in s["attrs"] and s["start_s"] >= (before[-1] if before else 0.0):
            by_q.setdefault(s["attrs"]["q"], []).append(s)
    shares = []
    for quantum in quanta:
        turn = by_q[quantum["attrs"]["q"]]
        names = {s["name"] for s in turn}
        if "program/first_call" in names or not {"serve/admit", "serve/commit"} <= names:
            continue
        begin = max(s["start_s"] for s in turn if s["name"] == "serve/admit")  # idle turns before it share its q
        end = max(s["start_s"] + s["dur_s"] for s in turn if s["name"] == "serve/commit")
        host = sum(own[s["id"]] for s in turn if s["name"] in HOST and s["start_s"] >= begin)
        shares.append(host / (end - begin))
    return 100.0 * median(shares) if shares else None
