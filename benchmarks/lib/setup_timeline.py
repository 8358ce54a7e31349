"""Set-up's timeline, for the four ``setup_*`` readers that read it
(``setup_engine_init_s``, ``setup_step_trace_s``, ``setup_step_compile_s``,
``setup_callers_programs_s``): where the seconds from
the process's start to the window's start went, by what the PROGRAM counted
where it spent them.

The rule every reader of set-up goes by: **a counter's value at the window's
start is its total since the process started less its rise over the window**
(``record["counters"]``, which ``drivers/train.py`` takes of every series of
the registry, labelled ones too, by ``lib/program.py::totals``). No span ring is
asked, so nothing can have fallen off it, and a first call INSIDE the window is
in the rise and therefore not in the result: a reader gives a number on every
tree that has the counters and None on one that lacks them (the parent of the
PR that added them). The totals are the process's own, read after the run, or
``record["program"]["counters"]`` (and ``["gauges"]``) where a test hands a
record over with them.

What the program counts (``docs/OBSERVABILITY.md``, "Set-up: what it writes,
and who reads it"):

- ``engine_init_seconds_total{part}``: the wall seconds of the trainer's
  construction (the span ``init/engine``) by part, ``mesh``, ``shard_state``,
  ``optimizer`` and ``rest``, and ``after``: the first calls of family
  ``init`` that lie outside it (what sets the engine's state up once it is
  built: the first compute copy's cast, the overflow count's two programs);
- ``program_first_call_seconds_total{family, phase}``: every
  ``program/first_call`` span's seconds by phase (``trace``, ``lower``,
  ``compile``, ``cache_fetch`` inside ``compile``, ``other``, and the caller's
  own: ``flops_count``, ``cost_card``), so that a family's phases but
  ``cache_fetch`` sum to its spans' wall seconds;
- ``program_trace|lower|compile_seconds_total``: the same phases of EVERY
  program of the process; less all families', what reached the backend in no
  first-call span: the caller's programs (``model.init``, the plain reference);
- the gauge ``import_seconds``: ``deepspeed_tpu/__init__.py`` top to bottom.

``timeline(record)`` cuts ``setup_s`` into parts that do not overlap:
``engine_init`` (a wall span and the ``after`` calls outside it),
``step_trace`` and ``step_compile`` (family ``train``'s first calls, which lie
in the warm-up steps), ``import``, ``callers_programs`` (wall seconds outside
every span of the program's) and ``harness``: ``setup_s`` less all of them (the
runtime's start, the batches, the reference's own execution, in a traced run
the profiler's start and the traced steps), which is why it is no metric: a
remainder is no measurement. Four of the parts are metrics, so on a result's
line ``harness`` is ``setup_s`` less the four readers' sum and the ``import``
gauge, and a difference in ``setup_s`` that none of the four shows lies there.
"""

import re
from typing import Dict, Optional

from benchmarks.lib import program

SERIES = re.compile(r'^(?P<name>\w+)\{(?P<labels>.*)\}$')
LABEL = re.compile(r'(\w+)="([^"]*)"')
BACKEND = ("trace", "lower", "compile")  # the phases JAX times of every program; ``cache_fetch`` lies inside ``compile``


def at_window_start(record: Dict) -> Optional[Dict[str, float]]:
    """Every counter series of the program's registry at the window's start: total less the window's rise."""
    if record.get("program") is not None:
        totals = record["program"].get("counters") or {}
    else:
        try:
            totals = program.totals()
        except ImportError:
            return None
    rise = record.get("counters") or {}
    return {series: total - rise.get(series, 0.0) for series, total in totals.items() if total is not None}


def by_labels(counters: Dict[str, float], name: str, *keys: str) -> Dict[tuple, float]:
    """``{(the values of the labels ``keys``): value}`` over the series of the family ``name``."""
    out = {}
    for series, value in counters.items():
        m = SERIES.match(series)
        if m and m.group("name") == name:
            labels = dict(LABEL.findall(m.group("labels")))
            out[tuple(labels.get(k) for k in keys)] = value
    return out


def _import_seconds(record: Dict) -> Optional[float]:
    if record.get("program") is not None:
        return (record["program"].get("gauges") or {}).get("import_seconds")
    from deepspeed_tpu.telemetry import get_registry

    return get_registry().peek("import_seconds")


def timeline(record: Dict) -> Optional[Dict]:
    """The parts of ``setup_s`` (see the module's docstring), also left in
    ``record["extras"]["setup_timeline_s"]``; None for a record with no
    ``setup_s`` or a program without the counters."""
    setup_s = (record.get("end_to_end") or {}).get("setup_s")
    counters = at_window_start(record) if setup_s is not None else None
    if not counters:
        return None
    parts = {part: s for (part,), s in by_labels(counters, "engine_init_seconds_total", "part").items()}
    calls = by_labels(counters, "program_first_call_seconds_total", "family", "phase")
    if not parts or not calls:
        return None
    train = {phase: s for (family, phase), s in calls.items() if family == "train"}
    in_spans = sum(s for (_, phase), s in calls.items() if phase in BACKEND)
    out = {"engine_init": sum(parts.values()),
           "step_trace": sum(s for phase, s in train.items() if phase not in ("compile", "cache_fetch")),
           "step_compile": train.get("compile", 0.0),
           "import": _import_seconds(record) or 0.0,
           "callers_programs": sum(counters.get(f"program_{phase}_seconds_total", 0.0) for phase in BACKEND) - in_spans}
    out["harness"] = setup_s - sum(out.values())
    out.update(engine_init_parts=parts, step_first_calls=train,
               init_first_calls={phase: s for (family, phase), s in calls.items() if family == "init"})
    record.setdefault("extras", {})["setup_timeline_s"] = out
    return out
