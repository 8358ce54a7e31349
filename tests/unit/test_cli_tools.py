"""CLI-surface parity: ds_tpu_bench (comm sweep), ds_tpu_ssh, ds_tpu_elastic
(reference bin/{ds_bench,ds_ssh,ds_elastic})."""

import json
import shlex

import numpy as np
import pytest

pytestmark = pytest.mark.fast


def test_comm_bench_sweep_runs():
    from deepspeed_tpu.benchmarks.comm_bench import format_table, run_comm_bench
    from deepspeed_tpu.parallel.mesh import initialize_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    topo = initialize_mesh(MeshConfig.from_dict({"data": 8}), force=True)
    res = run_comm_bench(ops=["all_reduce", "all_gather", "all_to_all", "reduce_scatter", "ppermute", "broadcast"],
                         axis="data", sizes_mb=[0.25], trials=3, warmups=1, topo=topo)
    assert len(res) == 6
    for r in res:
        assert r["world"] == 8 and r["time_us"] > 0 and r["algbw_gbps"] > 0
    ar = next(r for r in res if r["op"] == "all_reduce")
    assert ar["busbw_gbps"] == pytest.approx(ar["algbw_gbps"] * 2 * 7 / 8, rel=2e-2)  # values rounded to 3dp
    table = format_table(res)
    assert "all_reduce" in table and "busbw" in table


def test_comm_bench_cli_json(capsys):
    from deepspeed_tpu.benchmarks.comm_bench import main

    rc = main(["--ops", "all_reduce", "--sizes-mb", "0.25", "--trials", "2", "--json",
               "--mesh", '{"data": 8}'])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out and out[0]["op"] == "all_reduce"


def test_comm_bench_rejects_trivial_axis():
    from deepspeed_tpu.benchmarks.comm_bench import run_comm_bench
    from deepspeed_tpu.parallel.mesh import initialize_mesh
    from deepspeed_tpu.runtime.config import MeshConfig

    topo = initialize_mesh(MeshConfig.from_dict({"data": 8}), force=True)
    with pytest.raises(ValueError, match="nothing to benchmark"):
        run_comm_bench(axis="tensor", topo=topo)


def test_ds_ssh_dry_run(tmp_path, capsys):
    from deepspeed_tpu.launcher.ds_ssh import main

    hostfile = tmp_path / "hostfile"
    hostfile.write_text("worker-0 slots=4\nworker-1 slots=4\nworker-2 slots=4\n")
    rc = main(["-f", str(hostfile), "-e", "worker-2", "--dry-run", "hostname", "-f"])
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 2
    assert all("hostname -f" in l for l in lines)
    assert not any("worker-2" in l for l in lines)


def test_ds_ssh_missing_hostfile(tmp_path, capsys):
    from deepspeed_tpu.launcher.ds_ssh import main

    rc = main(["-f", str(tmp_path / "nope"), "--dry-run", "true"])
    assert rc == 1


def test_ds_elastic_cli(tmp_path, capsys):
    from deepspeed_tpu.elasticity.cli import main

    cfg = {
        "train_batch_size": 2048,
        "elasticity": {
            "enabled": True,
            "max_train_batch_size": 2048,
            "micro_batch_sizes": [2, 4, 8],
            "min_gpus": 1,
            "max_gpus": 64,
            "min_time": 0,
            "version": 0.1,
        },
    }
    p = tmp_path / "ds_config.json"
    p.write_text(json.dumps(cfg))
    rc = main(["-c", str(p), "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["global_batch"] > 0 and out["valid_chip_counts"]
    # every compatible chip count gets a full plan (micro x gas x chips == batch)
    for plan in out["plans"]:
        assert plan["micro_batch"] in (2, 4, 8)
        assert plan["micro_batch"] * plan["grad_accum"] * plan["chips"] == out["global_batch"]


# --------------------------------------------------------- perf_report CLI

def _load_perf_report():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "tools", "perf_report.py")
    spec = importlib.util.spec_from_file_location("perf_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _perf_artifact(tmp_path):
    """A snapshot file built from a REAL accountant snapshot, so the
    renderer is tested against the exact shape ``snapshot()`` returns."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.telemetry import PerfAccountant

    acct = PerfAccountant(mode=1, use_telemetry=False)
    w = acct.wrap("fused", jax.jit(lambda a, b: a @ b), meta={"kind": "fused_step", "chunk": 8})
    jax.block_until_ready(w(jnp.ones((8, 16), jnp.float32), jnp.ones((16, 4), jnp.float32)))
    acct.attribute(useful_tokens=6, slot_tokens=8)
    acct.note_spec(proposed=10, accepted=6)
    acct.note_cow(4096)
    acct.set_hbm(limit=10 ** 9, weights=10 ** 6, kv_pages=10 ** 5, prefix=10 ** 4)
    p = tmp_path / "PERF.json"
    p.write_text(json.dumps({"rung": "serve", "snapshots": {"serve": acct.snapshot()}}))
    return p


def test_perf_report_renders_roofline(tmp_path, capsys):
    mod = _load_perf_report()
    p = _perf_artifact(tmp_path)
    assert mod.main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "== serve ==" in out
    assert "fused[fused_step](chunk=8)" in out  # cost-card label with meta dims
    assert "flops/call" in out and "bound" in out  # roofline table headers
    assert "useful/slot tokens: 6/8" in out
    assert "4 rejected" in out  # spec ledger line
    assert "cow copies" in out
    assert "pressure" in out and "hbm pools" in out


def test_perf_report_rung_selection_and_json(tmp_path, capsys):
    mod = _load_perf_report()
    p = _perf_artifact(tmp_path)
    assert mod.main([str(p), "--rung", "serve"]) == 0
    capsys.readouterr()
    assert mod.main([str(p), "--rung", "nope"]) == 1  # unknown rung: error, not silence
    assert "not in artifact" in capsys.readouterr().err
    assert mod.main([str(p), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["serve"]["cards"][0]["program"] == "fused"


def test_perf_report_missing_file(tmp_path, capsys):
    mod = _load_perf_report()
    assert mod.main([str(tmp_path / "nope.json")]) == 1
    assert "cannot read" in capsys.readouterr().err


def test_perf_report_diff_rows_flag_a_drop_beyond_the_threshold():
    mod = _load_perf_report()
    a = {"tokens_per_sec": 100.0, "mfu": 0.5, "goodput_fraction": 0.5,
         "dispatches": 10.0}
    b = dict(a, tokens_per_sec=93.0, dispatches=10.4)
    by = {r["metric"]: r for r in mod.diff_rows(a, b, 0.05)}
    assert by["tokens_per_sec"]["regressed"] is True   # -7%, higher is better
    assert by["tokens_per_sec"]["pct"] == pytest.approx(-0.07)
    assert not by["dispatches"]["regressed"]            # +4%, lower is better
    assert not by["mfu"]["regressed"] and by["mfu"]["delta"] == 0.0
