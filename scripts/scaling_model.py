"""SCALING_MODEL.json generator (VERDICT r4 weak #5 / next-round #8).

For each parallelism layout on the 8-virtual-device CPU mesh, compile the
train step, extract every collective XLA emitted (exact per-device wire
bytes per axis — paddle_tpu.distributed.comm_analysis), and project
8 -> 256-chip efficiency over assumed v5e ICI/DCN bandwidths. The byte
counts are measurements of the compiled program; ONLY the bandwidths and
the overlap assumption are model inputs.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
     python scripts/scaling_model.py
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "SCALING_MODEL.json")

# ---- model assumptions (everything else is measured) ---------------------
ICI_BW_PER_CHIP = 1.6e11  # ~160 GB/s usable per v5e chip (4 ICI links)
DCN_BW_PER_CHIP = 3.1e9   # ~25 GB/s per 8-chip host across DCN
PEAK_BF16 = 197e12        # v5e bf16 peak FLOP/s
ASSUMPTIONS = {
    "ici_bw_per_chip_bytes_s": ICI_BW_PER_CHIP,
    "dcn_bw_per_chip_bytes_s": DCN_BW_PER_CHIP,
    "peak_bf16_flops": PEAK_BF16,
    "overlap": "both bounds reported: none (comm fully exposed) and "
               "full (comm hidden unless it exceeds compute)",
    "scaling_mode": "weak scaling: dp degree grows with chips, per-device "
                    "batch fixed, mp/pp/sep degrees fixed",
}

CONFIGS = {
    # name: (hybrid degrees, extra strategy keys, env)
    "dp8": ({"dp_degree": 8}, {}, {}),
    "mp8": ({"mp_degree": 8}, {}, {}),
    "dp2_mp4": ({"dp_degree": 2, "mp_degree": 4}, {}, {}),
    "sharding8_z1": ({"dp_degree": 1}, {"sharding_degree": 8}, {}),
    "dp2_pp2_mp2": ({"dp_degree": 2, "pp_degree": 2, "mp_degree": 2}, {},
                    {}),
    # same mesh, interleaved 1F1B with 2 virtual stages (2 chunks/stage of
    # the 4-layer probe) — the per-config JSON records both schedules'
    # bubble fractions side by side (docs/PIPELINE.md)
    "dp2_pp2_mp2_1f1b_v2": (
        {"dp_degree": 2, "pp_degree": 2, "mp_degree": 2}, {},
        {"PADDLE_TPU_PP_SCHEDULE": "1f1b,virtual=2"}),
    "2slice_dp2_mp4": ({"dp_degree": 2, "mp_degree": 4}, {},
                       {"PADDLE_TPU_NUM_SLICES": "2"}),
    # quantized-wire A/B of dp2_mp4: int8 activation recombination
    # (mp_comm) + int8 gradient wire (grad_comm) — the per_axis_wire
    # block prices what actually crosses each axis vs the f32 row above
    "dp2_mp4_int8": ({"dp_degree": 2, "mp_degree": 4}, {},
                     {"PADDLE_TPU_MP_COMM": "int8",
                      "PADDLE_TPU_GRAD_COMM": "int8"}),
}


def run_config(name):
    """Child process: build the step, compile, extract traffic."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import comm_analysis, fleet
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.text.models import GPTConfig, GPTForCausalLM

    import jax

    degrees, extra, _env = CONFIGS[name]
    # enable gauge recording (pp_* schedule telemetry is env-gated)
    if "PADDLE_TPU_TELEMETRY_DIR" not in os.environ:
        import tempfile

        os.environ["PADDLE_TPU_TELEMETRY_DIR"] = tempfile.mkdtemp(
            prefix="pt_scaling_telemetry_")
    s = fleet.DistributedStrategy()
    s.hybrid_configs.update(degrees)
    for k, v in extra.items():
        s.hybrid_configs[k] = v
    fleet.init(is_collective=True, strategy=s)
    paddle.seed(0)
    # GPT-1.3B layer GEOMETRY (hidden 2048, 16 heads) at 4 layers, seq 128:
    # per-layer comm structure identical to the full model; grads scale
    # linearly in layer count (noted in meta for extrapolation)
    cfg = GPTConfig(
        vocab_size=50304, hidden_size=2048, num_hidden_layers=4,
        num_attention_heads=16, intermediate_size=8192,
        max_position_embeddings=256, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    model = GPTForCausalLM(cfg).bfloat16()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    fleet.distributed_model(model)
    opt = fleet.distributed_optimizer(opt)
    step = fleet.DistTrainStep(model,
                               lambda m, ids, lbl: m(ids, labels=lbl), opt)
    ids = paddle.to_tensor(
        np.random.default_rng(0).integers(0, 50000, (8, 128))
        .astype(np.int32))
    t0 = time.perf_counter()
    comp = step._compiled_for(ids, ids)
    compile_s = time.perf_counter() - t0
    hlo = comp.as_text()
    mesh = _mesh.get_global_mesh()
    colls = comm_analysis.collective_traffic(hlo, mesh)
    per_axis = comm_analysis.axis_traffic_summary(colls)
    per_axis_payload = comm_analysis.axis_payload_summary(colls)
    per_axis_wire = comm_analysis.axis_wire_summary(colls)

    cost = comp.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops = float(dict(cost or {}).get("flops", 0.0))

    slices = _mesh._device_slice_ids(list(mesh.devices.flat), None)
    slice_of = {d.id: s_ for d, s_ in zip(mesh.devices.flat, slices)}
    crossing = comm_analysis.slice_crossing_traffic(hlo, mesh, slice_of)

    # pipeline-schedule attribution: compiled schedule, analytic + measured
    # (table idle-cell) bubble fractions, and the bucketed grad-exchange
    # bytes the backward can hide (docs/PIPELINE.md). Gauges are recorded
    # at trace time, so _compiled_for above already populated them.
    pipeline = None
    try:
        import paddle_tpu.observability as _obs
        from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel import (
            SpmdPipeline)

        pipe = next((sub for _p, sub in model.named_sublayers(include_self=True)
                     if isinstance(sub, SpmdPipeline)), None)
        if pipe is not None and degrees.get("pp_degree", 1) > 1:
            info = pipe.schedule_info(int(ids.shape[0]))
            pipeline = {
                "schedule": info["schedule"],
                "virtual_pp_degree": pipe.num_virtual_stages,
                "microbatches": info["M"],
                "analytic_bubble_fraction": round(
                    float(info["analytic_bubble_fraction"]), 4),
                "measured_bubble_fraction": round(
                    float(info["measured_bubble_fraction"]), 4),
                "overlap_hidden_bytes": int(
                    _obs.gauge("pp_overlap_hidden_bytes").value() or 0),
            }
    except Exception:
        pass

    print(json.dumps({
        "config": name, "compile_s": round(compile_s, 1),
        "n_collectives": len(colls),
        "per_axis_wire_bytes_per_device": per_axis,
        "per_axis_payload_bytes": per_axis_payload,
        "per_axis_wire": per_axis_wire,
        "flops_per_device_per_step": flops,
        "pipeline": pipeline,
        "cross_slice": [
            {**c, "axes": list(c["axes"])} for c in crossing],
    }), flush=True)


def project(entry):
    """8 -> N-chip efficiency under the stated assumptions.

    Single-slice (a v5e slice spans up to 256 chips all-ICI): every axis
    rides ICI; data-axis ring traffic per device is 2(n-1)/n*B and is
    scaled from the measured degree toward its asymptote. The separate
    multi-slice scenario (2 slices) uses the hierarchical schedule —
    intra-slice reduce-scatter, inter-slice shard exchange, intra-slice
    all-gather — whose per-chip DCN bytes are 2*payload/n_chips."""
    per_axis = entry["per_axis_wire_bytes_per_device"]
    payload = entry.get("per_axis_payload_bytes", {})
    flops = entry["flops_per_device_per_step"]
    compute_s = flops / PEAK_BF16

    def data_axis(axes):
        parts = axes.split("+")
        return "dp" in parts or "sharding" in parts

    data_degree = 1
    for axes, b in per_axis.items():
        if data_axis(axes):
            data_degree = max(data_degree, 2)  # measured at >=2 on the mesh
    out = {}
    for chips in (8, 16, 64, 256):
        ici = 0.0
        dp_payload = 0.0
        for axes, b in per_axis.items():
            if axes == "self":
                continue
            if data_axis(axes):
                # ring factor (n-1)/n: rescale measured degree -> scaled
                n0 = max(data_degree, 2)
                n1 = n0 * chips // 8
                b = b * ((n1 - 1) / n1) / ((n0 - 1) / n0)
                dp_payload += payload.get(axes, 0)
            ici += b
        comm_s = ici / ICI_BW_PER_CHIP
        entry_c = {
            "ici_bytes_per_chip": int(ici),
            "compute_s_ideal": compute_s,
            "comm_s_single_slice": comm_s,
            "efficiency_no_overlap": round(
                compute_s / (compute_s + comm_s), 4) if compute_s else None,
            "efficiency_full_overlap": round(min(
                1.0, compute_s / max(comm_s, 1e-12)), 4)
            if compute_s else None,
        }
        if chips == 256 and dp_payload:
            # 2-slice deployment: hierarchical dp all-reduce across DCN
            dcn_per_chip = 2 * dp_payload / chips
            dcn_s = dcn_per_chip / DCN_BW_PER_CHIP
            entry_c["two_slice"] = {
                "dcn_bytes_per_chip": int(dcn_per_chip),
                "comm_s": comm_s + dcn_s,
                "efficiency_no_overlap": round(
                    compute_s / (compute_s + comm_s + dcn_s), 4)
                if compute_s else None,
            }
        out[str(chips)] = entry_c
    return out


def record_planner_blocks(path=None):
    """Annotate each MULTICHIP_SCALING.json proxy entry with a ``planner``
    block: the auto-parallel cost model's predicted step time for the mesh
    that was actually measured, the relative error, and the layout the
    planner would have picked for that device count. Pure math over the
    checked-in measurements (docs/AUTOPLAN.md) — no subprocesses, safe to
    re-run any time the proxy numbers change."""
    sys.path.insert(0, REPO)
    from paddle_tpu.distributed.auto_parallel import planner

    path = path or os.path.join(REPO, "MULTICHIP_SCALING.json")
    with open(path) as f:
        doc = json.load(f)
    entries = doc.get("results", [])
    consts = planner.calibrate(entries)
    annotated = 0
    for e in entries:
        if not e.get("ok", True) or "step_s" not in e:
            continue
        mc = planner._entry_model(e, planner.ModelConfig())
        topo = planner.Topology(
            n_devices=int(e["n"]),
            num_slices=2 if e.get("two_slice") else 1)
        measured = planner.score(
            planner._entry_candidate(e), mc, topo, consts)
        block = {
            "predicted_step_s": round(measured.predicted_step_s, 4),
            "measured_step_s": e["step_s"],
            "rel_error": round(
                abs(measured.predicted_step_s - e["step_s"])
                / max(e["step_s"], 1e-12), 4),
        }
        try:
            best = planner.plan(mc, topo, constants=consts).best
            block["best"] = {
                "mesh": best.mesh_dict(), "schedule": best.schedule,
                "virtual_pp_degree": best.virtual_pp_degree,
                "microbatches": best.microbatches,
                "predicted_step_s": round(best.predicted_step_s, 4),
            }
        except ValueError:
            block["best"] = None
        e["planner"] = block
        annotated += 1
    doc["planner_calibration"] = {
        "fixed_s": consts.fixed_s,
        "sec_per_flop": consts.sec_per_flop,
        "sec_per_byte": consts.sec_per_byte,
        "sec_per_collective": consts.sec_per_collective,
        "sec_per_dp_over_byte": consts.sec_per_dp_over_byte,
        "source": consts.source,
        "max_rel_error": round(consts.max_rel_error, 4),
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({"written": path, "planner_entries": annotated,
                      "calibration_max_rel_error":
                      round(consts.max_rel_error, 4)}))
    return doc


def record_mpmd_block(path=None):
    """Measure the MPMD A/B proxies and record them (plus the stage plans
    the auto-parallel planner picks) under ``mpmd`` in
    MULTICHIP_SCALING.json:

      balanced   — the dp2×pp2 stack both ways: SPMD 1f1b (one program,
                   collective boundaries) vs MPMD [2,2] (per-stage
                   programs, tensor-queue boundaries). Same parameters,
                   same schedule — the delta is the execution model.
      unbalanced — a 6-layer stack split 5/1 across two stages, run
                   MPMD both ways: best equal widths [2,2] vs the
                   planner's unequal pick. Equal widths leave the heavy
                   stage the bottleneck every tick; the planner shifts
                   devices onto it.

    Caller must apply _cpu_mesh_flags BEFORE jax initializes (the
    ``--mpmd-only`` entry point does). Measured step times feed the next
    planner recalibration alongside the SPMD proxy entries."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.auto_parallel import planner
    from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel import (
        SpmdPipeline)
    from paddle_tpu.distributed.mpmd import MpmdPipeline

    D = 32

    def init(pp=2):
        s = fleet.DistributedStrategy()
        s.hybrid_configs = {"dp_degree": 8 // pp, "mp_degree": 1,
                            "pp_degree": pp}
        fleet.init(is_collective=True, strategy=s)

    def blocks(n, seed=0):
        paddle.seed(seed)
        return [nn.Sequential(nn.Linear(D, D), nn.Tanh()) for _ in range(n)]

    def timed(step_fn, steps=5, warmup=2):
        for _ in range(warmup):
            step_fn()
        ts = []
        for _ in range(steps):
            t0 = time.perf_counter()
            step_fn()
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    # -- balanced: SPMD 1f1b vs MPMD [2,2] over the same 8-layer stack ------
    init(2)
    pipe = SpmdPipeline(blocks(8), num_stages=2, num_microbatches=4,
                        num_virtual_stages=1, schedule="1f1b")
    paddle.seed(100)
    head = nn.Linear(D, 1)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=pipe.parameters() + head.parameters())
    xb = np.random.RandomState(0).randn(8, D).astype("float32")
    xt = paddle.to_tensor(xb)

    def spmd_step():
        loss = (head(pipe(xt)) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()

    spmd_s = timed(spmd_step)
    mp_bal = MpmdPipeline(pipe, [2, 2], head=head, schedule="1f1b")

    def mpmd_step():
        mp_bal.train_batch(xb)
        opt.step()
        opt.clear_grad()

    mpmd_s = timed(mpmd_step)
    bal_plan = planner.plan_mpmd_stages(
        planner.ModelConfig(layers=8, hidden=D, global_batch=8),
        planner.Topology(n_devices=4), num_stages=2, microbatches=4)
    balanced = {
        "stack": f"8x(Linear{D}+Tanh), batch 8, microbatches 4, 1f1b",
        "spmd_1f1b_step_s": round(spmd_s, 4),
        "mpmd_step_s": round(mpmd_s, 4),
        "widths": [2, 2],
        "planner": bal_plan.best.to_json(),
    }

    # -- unbalanced: 6 layers split 5/1; equal [2,2] vs planner's pick.
    # Hidden 512 so per-tick compute dwarfs dispatch overhead — that is
    # what lets the emulated mesh's genuine device-level concurrency show
    # the width effect instead of launch noise.
    DU = 512

    def unbal_step_s(widths):
        init(2)
        paddle.seed(0)
        p6 = SpmdPipeline(
            [nn.Sequential(nn.Linear(DU, DU), nn.Tanh()) for _ in range(6)],
            num_stages=2, num_microbatches=2,
            num_virtual_stages=1, schedule="1f1b")
        paddle.seed(100)
        h6 = nn.Linear(DU, 1)
        o6 = paddle.optimizer.AdamW(
            learning_rate=1e-3,
            parameters=p6.parameters() + h6.parameters())
        mp6 = MpmdPipeline(p6, widths, head=h6, schedule="1f1b",
                           layer_split=[5, 1])
        x6 = np.random.RandomState(1).randn(24, DU).astype("float32")

        def step():
            mp6.train_batch(x6)
            o6.step()
            o6.clear_grad()

        wall = timed(step)
        # device-parallel projection from the MEASURED per-stage busy
        # seconds: the emulation host serializes every device, so a
        # stage's busy_s is its total work regardless of width; on a
        # real fabric that work shards over dp_i devices and the step is
        # (M+S-1)/M bubble-stretched ticks of the bottleneck stage.
        # Same method as project(): measured inputs, stated-fabric model.
        S, M = mp6.num_stages, mp6.num_microbatches
        busy = {s_: st["busy_s"] for s_, st in mp6.last_step_stats.items()}
        proj = (1.0 + (S - 1) / M) * max(
            busy[s_] / w for s_, w in enumerate(widths))
        idle = {s_: round(st["idle_fraction"], 3)
                for s_, st in mp6.last_step_stats.items()}
        return wall, proj, busy, idle

    unbal_plan = planner.plan_mpmd_stages(
        planner.ModelConfig(layers=2, hidden=DU, global_batch=24),
        planner.Topology(n_devices=4), num_stages=2, microbatches=2,
        layer_costs=[5.0, 1.0])
    equal_widths = list(unbal_plan.best_equal.widths)
    unequal_widths = list(unbal_plan.best.widths)
    eq_wall, eq_proj, eq_busy, eq_idle = unbal_step_s(equal_widths)
    un_wall, un_proj, un_busy, un_idle = unbal_step_s(unequal_widths)
    unbalanced = {
        "stack": f"6x(Linear{DU}+Tanh) split 5/1, batch 24, "
                 "microbatches 2, 1f1b",
        "equal": {"widths": equal_widths,
                  "host_wall_step_s": round(eq_wall, 4),
                  "stage_busy_s": {str(k): round(v, 4)
                                   for k, v in eq_busy.items()},
                  "stage_idle_fraction": eq_idle,
                  "projected_step_s": round(eq_proj, 4),
                  "planner_predicted_step_s":
                  round(unbal_plan.best_equal.predicted_step_s, 4)},
        "unequal": {"widths": unequal_widths,
                    "host_wall_step_s": round(un_wall, 4),
                    "stage_busy_s": {str(k): round(v, 4)
                                     for k, v in un_busy.items()},
                    "stage_idle_fraction": un_idle,
                    "projected_step_s": round(un_proj, 4),
                    "planner_predicted_step_s":
                    round(unbal_plan.best.predicted_step_s, 4)},
        # winner on a device-parallel fabric, from measured busy seconds
        # (host wall clock on the 2-core emulation box rewards whichever
        # layout maxes out 2-way overlap, not the wider stage)
        "winner": "unequal" if un_proj < eq_proj else "equal",
        "predicted_winner": "unequal",
        "planner": unbal_plan.best.to_json(),
    }

    path = path or os.path.join(REPO, "MULTICHIP_SCALING.json")
    with open(path) as f:
        doc = json.load(f)
    doc["mpmd"] = {
        "note": "MPMD execution A/B on the 8-virtual-device CPU mesh "
                "(distributed.mpmd). Host-serialized timings — load-"
                "bearing results are the predicted per-width ranking "
                "and the unbalanced equal-vs-unequal delta; entries "
                "feed the next planner recalibration.",
        "balanced": balanced,
        "unbalanced": unbalanced,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps({"written": path, "mpmd": doc["mpmd"]}, indent=1))
    return doc


def main():
    results = {}
    for name in CONFIGS:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        kept = [t for t in env.get("XLA_FLAGS", "").split()
                if not t.startswith("--xla_force_host_platform_device_count")]
        env["XLA_FLAGS"] = " ".join(
            kept + ["--xla_force_host_platform_device_count=8"])
        env.update(CONFIGS[name][2])
        env["SCALING_MODEL_CHILD"] = name
        p = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           env=env, capture_output=True, text=True,
                           timeout=1200)
        lines = [l for l in p.stdout.splitlines() if l.startswith("{")]
        if not lines:
            results[name] = {"error":
                             f"rc={p.returncode}: {(p.stderr or '')[-300:]}"}
            continue
        entry = json.loads(lines[-1])
        entry["projection"] = project(entry)
        results[name] = entry
        print(f"[scaling_model] {name}: "
              f"{entry['n_collectives']} collectives, "
              f"axes={list(entry['per_axis_wire_bytes_per_device'])}",
              file=sys.stderr)
    doc = {
        "meta": {
            "generated_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime()),
            "model": "GPT-1.3B layer geometry (hidden 2048, 16 heads, "
                     "ffn 8192) at 4 layers, seq 128, batch 8, bf16; "
                     "grad/param traffic scales linearly in layer count",
            "assumptions": ASSUMPTIONS,
            "method": "wire bytes parsed from the compiled SPMD HLO "
                      "(paddle_tpu.distributed.comm_analysis); ring "
                      "algorithm cost model per collective",
            "note": "absolute efficiency figures are for THIS probe "
                    "geometry (per-device batch 1-4, seq 128) and "
                    "underestimate production configs: compute scales "
                    "linearly with per-device batch while dp gradient "
                    "traffic is batch-independent. The load-bearing "
                    "results are the per-axis byte table, the mp/pp "
                    "degree-invariance, and cross_slice == dp-gradient-"
                    "only.",
        },
        "configs": results,
    }
    with open(OUT, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(json.dumps({"written": OUT,
                      "configs": list(results)}))


if __name__ == "__main__":
    if "--planner-only" in sys.argv:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        record_planner_blocks()
        sys.exit(0)
    if "--mpmd-only" in sys.argv:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        sys.path.insert(0, REPO)
        import _cpu_mesh_flags

        _cpu_mesh_flags.apply()
        record_mpmd_block()
        sys.exit(0)
    child = os.environ.pop("SCALING_MODEL_CHILD", None)
    if child:
        import jax

        jax.config.update("jax_platforms", "cpu")
        sys.path.insert(0, REPO)
        run_config(child)
    else:
        main()
