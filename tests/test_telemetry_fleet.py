"""2-process telemetry acceptance run (docs/OBSERVABILITY.md §5).

Two OS processes go through the real launch CLI (rank negotiation, JAX
coordination service, heartbeat watchdog) with PADDLE_TPU_TELEMETRY_DIR
set. The run must leave behind, per rank, a JSONL event log and a
Prometheus textfile, plus rank 0's merged fleet_metrics.json carrying
step-time, compile-count, checkpoint-duration, and heartbeat-age series
for BOTH ranks.
"""
import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "telemetry_worker.py")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def test_two_process_run_exports_fleet_telemetry(tmp_path):
    tdir = tmp_path / "telemetry"
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PADDLE_TPU_TELEMETRY_DIR"] = str(tdir)
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nnodes", "2", "--master", f"127.0.0.1:{port}",
           "--heartbeat_interval", "0.2",
           WORKER, str(tmp_path / "ckpt")]
    procs = [subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, cwd=REPO)
             for _ in range(2)]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=300)
            outs.append((p.returncode, out, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rc, out, err in outs:
        assert rc == 0, f"rc={rc}\nstdout:{out[-800:]}\nstderr:{err[-2500:]}"
    assert any('{"ok": true}' in out for _, out, _ in outs)

    # -- per-rank exports ---------------------------------------------------
    for r in (0, 1):
        lines = (tdir / f"events_rank{r}.jsonl").read_text().splitlines()
        evs = [json.loads(l) for l in lines if l.strip()]
        assert all(e["rank"] == r for e in evs if e["kind"] != "fleet_aggregate")
        kinds = {e["kind"] for e in evs}
        assert {"init_parallel_env", "watchdog_start", "xla_compile",
                "checkpoint_save"} <= kinds, (r, sorted(kinds))

        prom = (tdir / f"metrics_rank{r}.prom").read_text()
        assert "paddle_tpu_train_step_seconds_count" in prom
        assert "paddle_tpu_xla_compile_total" in prom
        assert "paddle_tpu_checkpoint_save_seconds_count" in prom
        assert "paddle_tpu_heartbeat_age_seconds" in prom

    rank0_kinds = {e["kind"] for e in map(
        json.loads, (tdir / "events_rank0.jsonl").read_text().splitlines())}
    assert "fleet_aggregate" in rank0_kinds

    # -- the merged fleet document ------------------------------------------
    doc = json.loads((tdir / "fleet_metrics.json").read_text())
    assert doc["schema"] == 1
    assert doc["world_size"] == 2
    assert doc["missing_ranks"] == []
    assert set(doc["ranks"]) == {"0", "1"}

    agg = doc["aggregate"]
    for r in ("0", "1"):
        assert r in agg["train_step_seconds"][""]["per_rank"]
        assert r in agg["xla_compile_total"]["where=train_step"]["per_rank"]
        assert r in agg["checkpoint_save_seconds"][""]["per_rank"]
    # every rank self-reports its own heartbeat-age series
    for r in (0, 1):
        assert str(r) in agg["heartbeat_age_seconds"][f"rank={r}"]["per_rank"]
    # cross-rank stats materialized once >1 rank reported
    slot = agg["train_step_seconds"][""]
    assert {"min", "max", "mean", "min_rank", "max_rank"} <= set(slot)

    # per-rank histogram series keep the raw bounded reservoir
    h = doc["ranks"]["1"]["metrics"]["train_step_seconds"]["series"][""]
    assert h["count"] >= 1 and len(h["values"]) == h["count"] <= 256
