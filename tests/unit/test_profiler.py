"""Device-timeline profiler (telemetry/profiler.py): golden-fixture
parser exactness, capture lifecycle against a fake trace backend, the
ops-plane capture endpoint round-trip, the flight recorder's
manifest-linked + size-bounded profile section, and the telemetry_merge
profiler-summary path.

The fixture ``fixtures/tiny_device_trace.xspace.txt`` is a hand-written
XSpace in text form with the lanes a TPU v5e trace has (one plane a chip;
``XLA Modules``, ``XLA Ops``, ``Async XLA Ops``; the host's threads on
``/host:CPU``), made into the ``.xplane.pb`` a profiler session leaves by
``jax.profiler.ProfileData`` itself, so that every case reads it the way a
capture on the chip is read. Every category total is exact arithmetic:

- compute  [0,1000] + [1500,2000] + [2100,2200]  = 1600 us
- collective [800,1200] (an all-reduce from its start to its done, of which
  the ``-done`` holds the lane for [1000,1200]) + [2500,2800] (synchronous) = 700 us
  exposed (minus compute union): [1000,1200] + [2500,2800] = 500 us
- transfer [3000,3200] (a copy's start-to-done span and its ``-done``)  =  200 us
- device busy (what holds the operation lane)    = 2300 us
- the whole program's event (``XLA Modules``), the plane's other lines and
  the host's threads are excluded; a host event that is no span of the
  program's is not even read
"""

import importlib.util
import json
import os
import sys
import time

import pytest

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tiny_device_trace.xspace.txt")
US = 1e-6


def _land_fixture(trace_dir, stamp="2026_01_01"):
    """The fixture where and as jax would put a session's trace."""
    from jax.profiler import ProfileData
    dst = os.path.join(trace_dir, "plugins", "profile", stamp)
    os.makedirs(dst, exist_ok=True)
    with open(FIXTURE) as f, open(os.path.join(dst, "host.xplane.pb"), "wb") as out:
        out.write(ProfileData.text_proto_to_serialized_xspace(f.read()))


def _load_tool(name):
    root = os.path.join(os.path.dirname(__file__), "..", "..")
    spec = importlib.util.spec_from_file_location(
        f"{name}_cli", os.path.join(root, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh_profiler_singleton():
    from deepspeed_tpu.telemetry import profiler
    profiler._reset_for_tests()
    yield
    profiler._reset_for_tests()


# ------------------------------------------------------------------ parsing
class TestTraceParsing:
    @pytest.fixture(autouse=True)
    def _landed(self, tmp_path):
        _land_fixture(str(tmp_path))
        self.trace_dir = str(tmp_path)

    def _parsed(self):
        from deepspeed_tpu.telemetry import profiler
        return profiler.parse_trace_events(profiler.load_xplane(profiler.find_xplane(self.trace_dir)))

    def test_fixture_classifies_every_lane(self):
        parsed = self._parsed()
        cats = {}
        for e in parsed["events"]:
            cats[e["cat"]] = cats.get(e["cat"], 0) + 1
        # on the operation lane 3 compute, 2 collective (a -done and a synchronous one), 1 transfer (a -done); the two
        # start-to-done spans; the program's event; the plane's "Steps" line; of the host's two events the one span
        assert cats == {"compute": 3, "collective": 3, "transfer": 2,
                        "module": 1, "other": 1, "host": 1}

    def test_golden_waterfall_totals_exact(self):
        from deepspeed_tpu.telemetry import profiler
        summary = profiler.build_waterfall(self._parsed(), markers=[],
                                           window_s=4000 * US)
        t = summary["totals"]
        assert t["compute_s"] == pytest.approx(1600 * US)
        assert t["collective_s"] == pytest.approx(700 * US)
        assert t["collective_exposed_s"] == pytest.approx(500 * US)
        assert t["collective_overlapped_s"] == pytest.approx(200 * US)
        assert t["transfer_s"] == pytest.approx(200 * US)
        assert t["device_busy_s"] == pytest.approx(2300 * US)
        assert t["host_gap_s"] == pytest.approx(1700 * US)
        fr = summary["fractions"]
        assert fr["device_busy"] == pytest.approx(2300 / 4000)
        assert fr["host_gap"] == pytest.approx(1700 / 4000)
        assert fr["collective_exposed"] == pytest.approx(5 / 7, abs=1e-6)
        # top programs: compute only, ordered by device time
        assert summary["programs"][0] == ["fusion.1", pytest.approx(1000 * US)]
        assert [p[0] for p in summary["programs"]] == \
            ["fusion.1", "fusion.3", "dynamic-update-slice.7"]
        assert summary["collectives"]["trace_ops"] == 2

    def test_markers_cut_quanta_exact(self):
        """Two quantum markers split every category at the boundary."""
        from deepspeed_tpu.telemetry import profiler
        markers = [{"program": "fused_step", "rel_s": 2000 * US, "attrs": {}},
                   {"program": "fused_step", "rel_s": 4000 * US, "attrs": {}}]
        summary = profiler.build_waterfall(self._parsed(), markers,
                                           window_s=4000 * US)
        q0, q1 = summary["quanta"]
        assert q0["compute_s"] == pytest.approx(1500 * US)
        assert q0["collective_s"] == pytest.approx(400 * US)
        assert q0["collective_exposed_s"] == pytest.approx(200 * US)
        assert q0["transfer_s"] == 0.0
        assert q0["host_gap_s"] == pytest.approx(300 * US)
        assert q1["compute_s"] == pytest.approx(100 * US)
        assert q1["collective_s"] == pytest.approx(300 * US)
        assert q1["collective_exposed_s"] == pytest.approx(300 * US)
        assert q1["transfer_s"] == pytest.approx(200 * US)
        assert q1["host_gap_s"] == pytest.approx(1400 * US)
        # quantum rows recompose into the window totals
        for key in ("compute_s", "collective_s", "transfer_s", "host_gap_s"):
            assert q0[key] + q1[key] == pytest.approx(summary["totals"][key])

    def test_empty_trace_yields_zeroed_waterfall(self):
        from deepspeed_tpu.telemetry import profiler
        summary = profiler.build_waterfall(
            profiler.parse_trace_events({"planes": []}),
            markers=[], window_s=1.0)
        assert summary["totals"]["device_busy_s"] == 0.0
        assert summary["fractions"]["host_gap"] == 1.0
        assert summary["fractions"]["collective_exposed"] == 0.0

    def test_report_checker_accepts_golden(self):
        from deepspeed_tpu.telemetry import profiler
        trace_report = _load_tool("trace_report")
        summary = profiler.build_waterfall(self._parsed(), markers=[],
                                           window_s=4000 * US)
        assert trace_report.check_waterfall(summary) == []
        text = trace_report.render(summary)
        assert "fusion.1" in text and "exposed fraction" in text


# ---------------------------------------------------------------- lifecycle
def _fake_trace_seams(prof):
    """Swap the jax.profiler seams for a backend that lands the fixture
    where jax would put it."""
    prof._start_trace = _land_fixture
    prof._stop_trace = lambda: None
    return prof


class TestDeviceProfiler:
    def test_capture_lifecycle(self, tmp_path):
        from deepspeed_tpu.telemetry import get_registry
        from deepspeed_tpu.telemetry.profiler import DeviceProfiler
        prof = _fake_trace_seams(DeviceProfiler(out_dir=str(tmp_path), quanta=2))
        assert prof.state == "idle"
        prof.note_quantum("fused_step")  # idle: must be a no-op
        assert prof.status()["n_markers"] == 0
        assert prof.arm()
        prof.note_quantum("fused_step", rows=4)   # starts the trace
        assert prof.state == "tracing"
        prof.note_quantum("fused_step", rows=4)
        prof.note_quantum("fused_step", rows=3)   # reaches quanta=2 -> finalize
        assert prof.state == "idle"
        assert prof.captures == 1
        summary = prof.summary()
        assert summary["trace"] == "ok"
        assert summary["n_quanta"] == 2
        assert summary["totals"]["compute_s"] == pytest.approx(1600 * US)
        assert summary["quanta"][0]["attrs"] == {"rows": 4}
        # summary.json lands next to the raw trace
        with open(os.path.join(summary["trace_dir"], "summary.json")) as f:
            assert json.load(f)["n_quanta"] == 2
        # derived registry metrics are fractions in [0, 1]
        reg = get_registry()
        for name in ("profile_collective_exposed_fraction",
                     "profile_host_gap_fraction",
                     "profile_device_busy_fraction"):
            v = reg.peek(name)
            assert v is not None and 0.0 <= v <= 1.0, (name, v)
        assert reg.peek("profile_captures_total") >= 1

    def test_start_trace_failure_degrades_to_marker_summary(self, tmp_path):
        from deepspeed_tpu.telemetry.profiler import DeviceProfiler
        prof = DeviceProfiler(out_dir=str(tmp_path), quanta=2)

        def boom(_dir):
            raise RuntimeError("profiler already active")
        prof._start_trace = boom
        prof.arm()
        for _ in range(3):
            prof.note_quantum("decode")
        summary = prof.summary()
        assert summary["trace"] == "unavailable"
        assert summary["n_quanta"] == 2
        assert summary["totals"]["device_busy_s"] == 0.0
        assert summary["fractions"]["collective_exposed"] == 0.0

    def test_finish_closes_short_capture(self, tmp_path):
        from deepspeed_tpu.telemetry.profiler import DeviceProfiler
        prof = _fake_trace_seams(DeviceProfiler(out_dir=str(tmp_path),
                                                quanta=100))
        prof.arm()
        prof.note_quantum("fused_step")
        prof.note_quantum("fused_step")
        assert prof.state == "tracing"
        summary = prof.finish()
        assert prof.state == "idle"
        assert summary is not None and summary["n_quanta"] == 1

    def test_write_rank_summary_for_merge(self, tmp_path):
        from deepspeed_tpu.telemetry.profiler import DeviceProfiler
        prof = _fake_trace_seams(DeviceProfiler(out_dir=str(tmp_path / "cap"),
                                                quanta=1))
        prof.arm()
        prof.note_quantum("fused_step")
        prof.note_quantum("fused_step")
        path = prof.write_rank_summary(str(tmp_path / "merge"))
        assert os.path.basename(path).startswith("profile-rank")
        with open(path) as f:
            doc = json.load(f)
        assert doc["summary"]["n_quanta"] == 1
        assert "rank" in doc


# ---------------------------------------------------------------- ops plane
class TestOpsPlaneProfileEndpoints:
    def _handle(self, method, path, body=b""):
        from deepspeed_tpu.telemetry.ops_plane import OpsPlane
        status, _ctype, payload = OpsPlane().handle(method, path, body)
        return status, json.loads(payload.decode())

    def test_capture_round_trip(self, tmp_path):
        from deepspeed_tpu.telemetry import profiler
        status, doc = self._handle("GET", "/profile")
        assert status == 200 and doc["configured"] is False

        status, doc = self._handle("POST", "/profile/capture",
                                   json.dumps({"quanta": 2}).encode())
        assert status == 201 and doc["armed"] is True
        assert doc["quanta_target"] == 2

        prof = _fake_trace_seams(profiler.get_device_profiler())
        prof.out_dir = str(tmp_path)
        for _ in range(3):
            profiler.note_quantum("fused_step", rows=2)

        status, doc = self._handle("GET", "/profile")
        assert status == 200
        assert doc["configured"] is True and doc["state"] == "idle"
        summary = doc["summary"]
        assert summary["n_quanta"] == 2
        assert 0.0 <= summary["fractions"]["collective_exposed"] <= 1.0
        assert summary["totals"]["compute_s"] > 0

    def test_capture_bad_body_and_conflict(self, tmp_path):
        from deepspeed_tpu.telemetry import profiler
        status, doc = self._handle("POST", "/profile/capture", b"not json")
        assert status == 400
        prof, armed = profiler.request_capture(quanta=4)
        assert armed
        _fake_trace_seams(prof)
        prof.out_dir = str(tmp_path)
        profiler.note_quantum("decode")       # trace now running
        status, doc = self._handle("POST", "/profile/capture")
        assert status == 409
        prof.finish()

    def test_root_lists_profile_endpoints(self):
        status, doc = self._handle("GET", "/")
        assert "/profile" in doc["endpoints"]
        assert "/profile/capture (POST)" in doc["endpoints"]


# ------------------------------------------------------------ flight linkage
class TestFlightProfileSection:
    def _recorder(self, tmp_path, monkeypatch, profile_s=0.05):
        import jax

        from deepspeed_tpu.telemetry.flight import FlightRecorder

        monkeypatch.setattr(jax.profiler, "start_trace", lambda trace_dir: _land_fixture(trace_dir, "t"))
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        return FlightRecorder(str(tmp_path), max_captures=4,
                              profile_s=profile_s)

    def _wait_profile(self, rec, name, timeout_s=5.0):
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            manifest = rec.read_manifest(name)
            if manifest and "profile" in manifest:
                return manifest
            time.sleep(0.05)
        raise AssertionError("profile section never landed in manifest")

    def test_manifest_links_profile_by_relative_path(self, tmp_path, monkeypatch):
        rec = self._recorder(tmp_path, monkeypatch)
        cap = rec.capture(reason="unit")
        manifest = self._wait_profile(rec, os.path.basename(cap))
        section = manifest["profile"]
        assert section["dir"] == "profile"
        assert section["dropped"] is False
        assert section["bytes"] > 0
        assert os.path.isdir(os.path.join(cap, section["dir"]))
        # the parsed waterfall summary rides the manifest
        assert section["summary"]["totals"]["compute_s"] == pytest.approx(1600 * US)

    def test_oversized_profile_dropped_and_counted(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DS_TPU_FLIGHT_PROFILE_MAX_MB", "0.0000001")
        rec = self._recorder(tmp_path, monkeypatch)
        cap = rec.capture(reason="unit")
        manifest = self._wait_profile(rec, os.path.basename(cap))
        section = manifest["profile"]
        assert section["dropped"] is True
        assert section["dir"] is None
        assert section["bytes"] > section["max_bytes"]
        assert not os.path.isdir(os.path.join(cap, "profile"))
        # the summary was parsed BEFORE the raw trace was dropped
        assert section["summary"]["totals"]["compute_s"] == pytest.approx(1600 * US)


# ----------------------------------------------------------- telemetry_merge
class TestTelemetryMergeProfiles:
    def test_json_verdict_carries_per_rank_exposed_fraction(self, tmp_path, capsys):
        from deepspeed_tpu.telemetry.agg import write_rank_snapshot
        from deepspeed_tpu.telemetry.registry import MetricsRegistry
        from deepspeed_tpu.telemetry.profiler import DeviceProfiler

        reg = MetricsRegistry()
        reg.counter("train_steps_total").inc(3)
        write_rank_snapshot(str(tmp_path), registry=reg)
        prof = _fake_trace_seams(DeviceProfiler(out_dir=str(tmp_path / "cap"),
                                                quanta=1))
        prof.arm()
        prof.note_quantum("fused_step")
        prof.note_quantum("fused_step")
        prof.write_rank_summary(str(tmp_path))

        merge = _load_tool("telemetry_merge")
        rc = merge.main([str(tmp_path), "--json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["verdict"] == "clean"
        assert "straggler_report" in doc
        ranks = doc["profiles"]
        assert len(ranks) == 1
        row = next(iter(ranks.values()))
        assert 0.0 <= row["collective_exposed_fraction"] <= 1.0
        assert row["trace"] == "ok"
