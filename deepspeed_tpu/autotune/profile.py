"""Tuned device profiles: the output of an autotune run.

A profile is one JSON file per device kind (``profiles/tpu-v5-lite.json``,
``profiles/cpu.json``, ...; none is committed) holding the winning knob vector plus
enough provenance to audit it: the engine fingerprint of the session it
was tuned on, a hash of the recorded trace, and the objective it won
with against the default vector. The engine loads it through the
``DS_TPU_TUNED_PROFILE`` knob and installs the vector as a knob-registry
*overlay* (analysis/knobs.py), so per-knob precedence is uniformly
``explicit env > profile > default`` and ``/varz`` can attribute every
knob to its source.
"""

import dataclasses
import hashlib
import json
import os
from dataclasses import dataclass, field
from typing import Dict, Optional

from ..analysis import knobs as _knobs
from ..utils.logging import logger

_SCHEMA = 1


def _sha(payload) -> str:
    return hashlib.sha256(json.dumps(payload, sort_keys=True, default=str)
                          .encode()).hexdigest()[:16]


def device_kind() -> str:
    """Sanitized accelerator kind for profile file names ('tpu-v4', 'cpu')."""
    try:
        import jax
        kind = jax.devices()[0].device_kind
    except Exception:
        kind = "unknown"
    return "".join(c if c.isalnum() or c in "-._" else "-" for c in
                   str(kind).strip().lower().replace(" ", "-")) or "unknown"


@dataclass
class TunedProfile:
    """One tuned operating point: knob vector + provenance."""

    device_kind: str
    knobs: Dict[str, str]                 # env-spelled knob vector
    engine_fingerprint: str               # hash of the source session's engine+model header
    trace_provenance: str                 # hash of the recorded trace (arrivals + digests)
    objective: str = "goodput"
    score: Optional[float] = None         # winner's objective on the trace
    baseline_score: Optional[float] = None  # default vector on the same trace
    constraint: Dict = field(default_factory=dict)  # e.g. {"ttft_p99_s": 1.0}
    source: str = "tools/autotune_serve.py"
    schema: int = _SCHEMA

    def provenance_hash(self) -> str:
        """Identity of this operating point: knobs + what it was tuned on."""
        return _sha({"knobs": self.knobs, "engine": self.engine_fingerprint,
                     "trace": self.trace_provenance})

    def to_dict(self) -> Dict:
        d = dataclasses.asdict(self)
        d["provenance_hash"] = self.provenance_hash()
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "TunedProfile":
        d = dict(d)
        d.pop("provenance_hash", None)
        names = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - names
        if unknown:
            raise ValueError(f"tuned profile has unknown fields {sorted(unknown)}")
        prof = cls(**d)
        prof.knobs = {str(k): str(v) for k, v in (prof.knobs or {}).items()}
        for name in prof.knobs:
            if not _knobs.is_declared(name):
                raise KeyError(f"tuned profile sets undeclared knob {name}")
        return prof


def session_fingerprint(session) -> str:
    """Engine+model identity of a recorded session (profile provenance)."""
    header = session.header or {}
    return _sha({"engine": header.get("engine"), "model_cfg": header.get("model_cfg")})


def trace_hash(session) -> str:
    """Identity of the recorded workload: arrivals + committed digests."""
    reqs = {int(u): {"arrival_s": r.get("arrival_s"), "prompt_len": len(r.get("prompt", [])),
                     "max_new_tokens": r.get("max_new_tokens")}
            for u, r in session.requests.items()}
    return _sha({"requests": reqs, "digests": session.digests()})


def save_profile(profile: TunedProfile, path: str) -> str:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(profile.to_dict(), f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def load_profile(path: str) -> TunedProfile:
    with open(path) as f:
        return TunedProfile.from_dict(json.load(f))


def profile_path_for(kind: Optional[str] = None, root: Optional[str] = None) -> str:
    root = root or os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "profiles")
    return os.path.join(root, f"{kind or device_kind()}.json")


# one profile install per (path) — reloading the same file is a no-op,
# switching paths swaps the overlay
_LOADED_PATH: Optional[str] = None


def maybe_load_tuned_profile(force: bool = False) -> Optional[TunedProfile]:
    """Install the tuned profile named by ``DS_TPU_TUNED_PROFILE``.

    ``'auto'`` resolves ``profiles/<device_kind>.json`` (silently absent
    when no profile was ever tuned for this device). Returns the
    installed profile, or None when the knob is unset / resolves to
    nothing. Idempotent per path; explicit env knobs always shadow the
    overlay, so load order cannot change an operator's explicit choice.
    """
    global _LOADED_PATH
    spec = _knobs.get_str("DS_TPU_TUNED_PROFILE")
    if not spec:
        if _LOADED_PATH is not None:
            _knobs.clear_profile()
            _LOADED_PATH = None
        return None
    path = profile_path_for() if spec.strip().lower() == "auto" else spec
    if spec.strip().lower() == "auto" and not os.path.exists(path):
        return None
    if not force and path == _LOADED_PATH:
        meta = _knobs.active_profile() or {}
        prof_d = meta.get("profile")
        return TunedProfile.from_dict(prof_d) if prof_d else None
    profile = load_profile(path)
    _knobs.set_profile(dict(profile.knobs), meta={
        "path": path,
        "device_kind": profile.device_kind,
        "provenance_hash": profile.provenance_hash(),
        "profile": profile.to_dict(),
    })
    _LOADED_PATH = path
    logger.info(f"tuned profile {path} installed ({len(profile.knobs)} knobs, "
                f"provenance {profile.provenance_hash()})")
    return profile


def profile_provenance() -> Optional[Dict]:
    """The active tuned profile as the ops plane reports it: file, knob
    vector, provenance hash, and which knobs an explicit env overrode."""
    meta = _knobs.active_profile()
    if meta is None:
        return None
    return {"path": meta.get("path"),
            "device_kind": meta.get("device_kind"),
            "provenance_hash": meta.get("provenance_hash"),
            "knobs": meta.get("knobs"),
            "env_overridden": meta.get("env_overridden")}
