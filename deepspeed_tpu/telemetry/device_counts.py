"""Numbers a step program works out on the device, brought to the registry
with no host callback in the program.

A ``jax.debug.callback`` makes an executable one that the persistent compile
cache will not keep ("uses host callbacks"), so every process would compile
the step anew. Instead the traced code hands its array over with ``report``,
the engine that traces the loss gathers what was reported (``collecting``) and
returns it as one more output of the step beside the loss, and on the host
``count`` gives each array to the function that was named for it, once the
step that made it has ended: nothing waits for it.
Where nobody collects (a plain ``model.apply``, an evaluation), ``report`` does
nothing.
"""

import contextlib
import contextvars
from typing import Callable, Dict

import numpy as np

_COLLECTING: contextvars.ContextVar = contextvars.ContextVar("ds_device_counts", default=None)
_ON_HOST: Dict[str, Callable] = {}  # name -> what takes the array on the host


@contextlib.contextmanager
def collecting():
    """While a loss is traced: a dict that fills with what the model reports.
    Return it from the traced function (it holds tracers)."""
    reported: Dict[str, object] = {}
    token = _COLLECTING.set(reported)
    try:
        yield reported
    finally:
        _COLLECTING.reset(token)


def report(name: str, value, on_host: Callable) -> None:
    """Traced code: ``value`` leaves the program under ``name``; ``on_host``
    will be called with it as a numpy array once a step."""
    reported = _COLLECTING.get()
    if reported is not None:
        reported[name] = value
        _ON_HOST[name] = on_host


def count(reported) -> None:
    """On the host: hand every reported array to its function (this waits for
    the program that made them, where it has not ended)."""
    for name, value in reported.items():
        _ON_HOST[name](np.asarray(value))
