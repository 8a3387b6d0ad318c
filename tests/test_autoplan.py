"""Cost-model auto-parallel planner (docs/AUTOPLAN.md,
paddle_tpu/distributed/auto_parallel/planner.py).

Tier-1 is pure math — enumeration legality, memory pruning, calibration
accuracy against the checked-in MULTICHIP_SCALING.json, manual-knob
precedence, and the never-raise contract of ``apply_auto_plan``. The
auto-planned end-to-end trajectory (fleet.init on 8 virtual devices with
``PADDLE_TPU_AUTO_PLAN=1``) is subprocess-isolated in the slow tier.
"""
import json
import os
import subprocess
import sys

import pytest

from paddle_tpu.distributed.auto_parallel import planner
from paddle_tpu.distributed.fleet import DistributedStrategy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCALING = os.path.join(REPO, "MULTICHIP_SCALING.json")


def _entries():
    with open(SCALING) as f:
        return [e for e in json.load(f)["results"]
                if e.get("ok") and not e.get("two_slice")]


# ---------------------------------------------------------------------------
# enumeration legality
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("ndev", [8, 16, 32])
def test_enumeration_is_divisibility_legal(ndev):
    mc = planner.ModelConfig(global_batch=2 * ndev)
    cands = planner.enumerate_candidates(mc, planner.Topology(n_devices=ndev))
    assert cands
    for c in cands:
        assert c.dp * c.mp * c.pp * c.sharding == ndev
        assert mc.heads % c.mp == 0 and mc.hidden % c.mp == 0
        assert mc.layers % c.pp == 0
        assert mc.global_batch % (c.dp * c.sharding) == 0
        if c.pp > 1:
            assert mc.layers % (c.pp * c.virtual_pp_degree) == 0
        else:
            assert c.schedule == "gpipe" and c.virtual_pp_degree == 1


def test_pinned_knobs_restrict_enumeration():
    mc = planner.ModelConfig(global_batch=16)
    cands = planner.enumerate_candidates(
        mc, planner.Topology(n_devices=8), pinned={"mp": 2, "pp": 2})
    assert cands and all(c.mp == 2 and c.pp == 2 for c in cands)
    with pytest.raises(ValueError):
        planner.plan(mc, planner.Topology(n_devices=8),
                     pinned={"mp": 3})  # 3 divides neither heads nor 8


# ---------------------------------------------------------------------------
# memory bound
# ---------------------------------------------------------------------------
def test_memory_prune_drops_unsharded_layouts():
    mc = planner.ModelConfig(global_batch=16)
    # bound chosen so dp-only (full replica + full f32 moments) cannot
    # fit but moment-sharded layouts can
    need_dp = planner.memory_bytes(
        planner.Candidate(dp=8, mp=1, pp=1, sharding=1), mc)
    topo = planner.Topology(n_devices=8, hbm_bytes=need_dp * 0.9)
    result = planner.plan(mc, topo)
    assert result.pruned_memory > 0
    assert result.best.sharding * result.best.mp * result.best.pp > 1
    with pytest.raises(ValueError):
        planner.plan(mc, planner.Topology(n_devices=8, hbm_bytes=1024))


def test_remat_policy_shrinks_activation_memory():
    mc = planner.ModelConfig(global_batch=16)
    c = planner.Candidate(dp=2, mp=2, pp=2, sharding=1)
    none = planner.memory_bytes(c, mc)
    sel = planner.memory_bytes(c, planner.ModelConfig(
        global_batch=16, remat="selective"))
    full = planner.memory_bytes(c, planner.ModelConfig(
        global_batch=16, remat="full"))
    assert none > sel > full


# ---------------------------------------------------------------------------
# calibration against the measured proxies
# ---------------------------------------------------------------------------
def test_calibration_within_15pct_of_measured():
    entries = _entries()
    assert len(entries) >= 3
    consts = planner.calibrate(entries)
    assert consts.max_rel_error <= 0.15
    for e in entries:
        mc = planner._entry_model(e, planner.ModelConfig())
        topo = planner.Topology(n_devices=int(e["n"]))
        pred = planner.score(planner._entry_candidate(e), mc, topo, consts)
        rel = abs(pred.predicted_step_s - e["step_s"]) / e["step_s"]
        assert rel <= 0.15, (e["n"], pred.predicted_step_s, e["step_s"])


def test_calibrated_constants_are_nonnegative_and_rank():
    consts = planner.load_calibration(path=SCALING)
    v = consts.as_vector()
    assert (v >= 0).all() and v.sum() > 0
    # ranking sanity at n=8: the planner's pick must score no worse than
    # the measured config under its own model
    mc = planner.ModelConfig(global_batch=16)
    result = planner.plan(mc, planner.Topology(n_devices=8),
                          constants=consts)
    measured = planner.score(
        planner.Candidate(dp=1, mp=2, pp=2, sharding=2, schedule="1f1b",
                          virtual_pp_degree=2, microbatches=2),
        mc, planner.Topology(n_devices=8), consts)
    assert result.best.predicted_step_s <= measured.predicted_step_s
    # breakdown is an exact decomposition of the prediction
    assert abs(sum(result.best.breakdown.values())
               - result.best.predicted_step_s) < 1e-9


def test_bubble_model_matches_schedule_table():
    mc = planner.ModelConfig()  # 4 layers
    c = planner.Candidate(dp=1, mp=2, pp=2, sharding=2, schedule="1f1b",
                          virtual_pp_degree=2, microbatches=2)
    # S=2, V=2, M=2: fill=(2-1)/2, fb=3*2+3*0.5 -> bubble = 1.5/7.5 = 0.2
    assert planner._bubble(c, mc) == pytest.approx(0.2)
    zb = planner.Candidate(dp=1, mp=2, pp=2, sharding=2,
                           schedule="zero_bubble", virtual_pp_degree=2,
                           microbatches=2)
    # zero_bubble: max(0, 2*0.5 - 2) = 0
    assert planner._bubble(zb, mc) == pytest.approx(0.0)


# ---------------------------------------------------------------------------
# strategy integration (manual settings always win; never raises)
# ---------------------------------------------------------------------------
def test_auto_strategy_flag():
    s = DistributedStrategy()
    assert not s.auto_plan
    a = DistributedStrategy.auto({"hidden": 128})
    assert a.auto_plan
    assert a.auto_plan_configs["model_config"] == {"hidden": 128}


def test_apply_auto_plan_fills_unset_knobs():
    s = DistributedStrategy()
    result = planner.apply_auto_plan(s, ndev=8)
    assert result is not None
    hc = s.hybrid_configs
    assert (hc["dp_degree"] * hc["mp_degree"] * hc["pp_degree"]
            * hc["sharding_degree"]) == 8
    for key, attr in (("dp_degree", "dp"), ("mp_degree", "mp"),
                      ("pp_degree", "pp"), ("sharding_degree", "sharding")):
        assert hc[key] == getattr(result.best, attr)
    assert s.pipeline_configs["schedule"] == result.best.schedule
    assert s.pipeline == (result.best.pp > 1)


def test_apply_auto_plan_respects_manual_pins():
    s = DistributedStrategy()
    s.hybrid_configs["mp_degree"] = 2
    s.pipeline_configs["schedule"] = "1f1b"
    result = planner.apply_auto_plan(s, ndev=8)
    assert result is not None
    assert s.hybrid_configs["mp_degree"] == 2
    assert s.pipeline_configs["schedule"] == "1f1b"


def test_axis_bytes_priced_at_wire_dtype():
    """ISSUE 13 satellite: the per-axis byte model prices quantized axes
    at the wire itemsize, and the plan records which dtypes it assumed."""
    mc32 = planner.ModelConfig()
    mcq = planner.ModelConfig(mp_wire="int8", grad_wire="bf16",
                              zero_gather_wire="bf16")
    cand = planner.Candidate(dp=2, mp=2, sharding=2)
    ax32 = planner._axis_bytes(cand, mc32)
    axq = planner._axis_bytes(cand, mcq)
    assert axq["mp"] == ax32["mp"] / 4          # int8 wire: 1/4 the bytes
    assert axq["dp"] == ax32["dp"] / 2          # bf16 grads: half
    # ZeRO legs: gather bf16 + scatter bf16 vs f32+f32
    assert axq["sharding"] == ax32["sharding"] / 2
    scored = planner.score(cand, mcq, planner.Topology(),
                           planner.CostConstants())
    assert scored.wire_dtypes == {
        "mp": "int8", "dp": "bf16", "zero_gather": "bf16"}
    # a quantized-wire model must never predict MORE comm time
    s32 = planner.score(cand, mc32, planner.Topology(),
                        planner.CostConstants())
    assert scored.breakdown["comm_s"] <= s32.breakdown["comm_s"]


def test_apply_auto_plan_prices_strategy_wires(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_MP_COMM", "int8")
    monkeypatch.delenv("PADDLE_TPU_GRAD_COMM", raising=False)
    s = DistributedStrategy()
    result = planner.apply_auto_plan(s, ndev=8)
    assert result is not None
    assert result.best.wire_dtypes["mp"] == "int8"
    # ZeRO param gathers are floored at bf16 on an int8 activation wire
    assert result.best.wire_dtypes["zero_gather"] == "bf16"
    monkeypatch.delenv("PADDLE_TPU_MP_COMM", raising=False)


def test_apply_auto_plan_never_raises():
    s = DistributedStrategy()
    s.hybrid_configs["mp_degree"] = 3  # divides neither heads nor devices
    before = dict(s.hybrid_configs)
    assert planner.apply_auto_plan(s, ndev=8) is None
    assert dict(s.hybrid_configs) == before  # untouched on failure


def test_plan_is_fast_and_ranked():
    import time
    t0 = time.perf_counter()
    result = planner.plan(planner.ModelConfig(global_batch=16),
                          planner.Topology(n_devices=8))
    assert time.perf_counter() - t0 < 1.0
    steps = [c.predicted_step_s for c in result.candidates]
    assert steps == sorted(steps) and len(steps) > 10


# ---------------------------------------------------------------------------
# slow tier: auto-planned e2e trajectory on 8 virtual devices
# ---------------------------------------------------------------------------
_E2E = """
import json, os
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.text.models.gpt import GPTConfig, GPTForCausalLM

s = fleet.DistributedStrategy()
manual = os.environ.get("E2E_MANUAL")
if manual:
    dp, mp, pp, sh = (int(x) for x in manual.split(","))
    s.hybrid_configs.update(dp_degree=dp, mp_degree=mp, pp_degree=pp,
                            sharding_degree=sh)
fleet.init(is_collective=True, strategy=s)
paddle.seed(0)
model = GPTForCausalLM(GPTConfig(
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, max_position_embeddings=64,
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
fleet.distributed_model(model)
opt = fleet.distributed_optimizer(opt)
step = fleet.DistTrainStep(model, lambda m, i, l: m(i, labels=l), opt)
rng = np.random.RandomState(0)
ids = paddle.to_tensor(rng.randint(0, 256, (8, 32)).astype(np.int32))
losses = [float(step(ids, ids)) for _ in range(3)]
hc = s.hybrid_configs
print(json.dumps({"losses": losses,
                  "mesh": {k: int(hc[k]) for k in
                           ("dp_degree", "mp_degree", "pp_degree",
                            "sharding_degree")}}))
"""


def _run_e2e(env_extra):
    env = dict(os.environ)
    env.pop("PADDLE_TPU_AUTO_PLAN", None)
    env["JAX_PLATFORMS"] = "cpu"
    kept = [t for t in env.get("XLA_FLAGS", "").split()
            if not t.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        kept + ["--xla_force_host_platform_device_count=8"])
    env["PYTHONPATH"] = REPO
    env.update(env_extra)
    p = subprocess.run([sys.executable, "-c", _E2E], env=env,
                       capture_output=True, text=True, timeout=600)
    lines = [l for l in p.stdout.splitlines() if l.startswith("{")]
    assert p.returncode == 0 and lines, (
        f"e2e child rc={p.returncode}: {p.stderr[-500:]}")
    return json.loads(lines[-1])


@pytest.mark.slow
def test_auto_planned_trajectory_matches_manual_mesh():
    auto = _run_e2e({"PADDLE_TPU_AUTO_PLAN": "1"})
    manual = _run_e2e({"E2E_MANUAL": "1,2,2,2"})  # the measured proxy mesh
    m = auto["mesh"]
    assert (m["dp_degree"] * m["mp_degree"] * m["pp_degree"]
            * m["sharding_degree"]) == 8
    # the planner must actually parallelize, not fall back to trivial
    assert m["pp_degree"] * m["sharding_degree"] * m["mp_degree"] > 1
    # SPMD degree-independence: fixed-batch trajectory matches the
    # hand-picked mesh step for step
    for a, b in zip(auto["losses"], manual["losses"]):
        assert abs(a - b) < 1e-4, (auto, manual)
