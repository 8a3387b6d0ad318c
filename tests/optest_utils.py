"""OpTest-analogue harness (VERDICT r4 #5; reference pattern:
test/legacy_test/op_test.py — every op checked against a numeric oracle).

Walks ``OP_REGISTRY``, synthesizes inputs for each op (generic
signature-driven synthesis + a per-op override table for ops with
structured inputs, the analogue of upstream OpTest's per-op ``setUp``),
and checks the eager tape's analytic gradients against central-difference
numeric gradients of the op's own forward.

Every registry op lands in exactly one bucket:

- ``checked``     — forward synthesized, float outputs, gradient verified
- ``non_float``   — no float output (integer/bool/complex results)
- ``stochastic``  — forward is randomized; no numeric oracle exists
- ``skipped``     — in the EXPLICIT ``SKIP`` table, with a reason

An op that fails synthesis without being in ``SKIP`` is a test failure:
the skip list stays honest (no silent holes).

A "spec" is ``(args, kwargs)`` whose leaves may be numpy arrays
(float32 arrays are the differentiable slots; int/bool arrays become
stop_gradient tensors) or plain python values passed through verbatim
(jax PRNG keys ride through as plain values).
"""
from __future__ import annotations

import inspect

import jax
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.framework.op import OP_REGISTRY

_rng = np.random.default_rng(20260801)


def _f(shape, lo=0.35, hi=0.85):
    return (_rng.random(shape) * (hi - lo) + lo).astype(np.float32)


def _fsep(shape):
    """Well-separated values (a shuffled grid, min gap 0.05): max/top-k
    style ops have valid central differences only when the perturbation
    cannot flip the argmax."""
    n = int(np.prod(shape))
    vals = (np.arange(n, dtype=np.float32) * 0.05)
    _rng.shuffle(vals)
    return vals.reshape(shape)


def _spd(n):
    a = _f((n, n))
    return (a @ a.T + np.eye(n, dtype=np.float32) * 2.0).astype(np.float32)


def _ids(shape, hi):
    return _rng.integers(0, hi, shape).astype(np.int32)


def _key():
    return jax.random.PRNGKey(7)


# ----------------------------------------------------------------------
# explicit skip table: op name -> justification
# ----------------------------------------------------------------------
SKIP = {
    # --- gradients intentionally not defined / not meaningful -----------
    "nextafter": "no JAX differentiation rule (piecewise-constant ULP step)",
    "quantized_matmul": "int8 operands; dequantized output has no grad path",
    "weight_only_linear": "int8/int4 weights; grad path covered by "
                          "test_nn_quant.py",
    "viterbi_decode_op": "argmax decode — piecewise constant output",
    "histc_op": "integer bin counts, piecewise-constant in x (grad 0 "
                "a.e.); bin-edge crossings make the numeric oracle invalid",
    "histogramdd_op": "same piecewise-constant counts as histc",
    "bernoulli_op": "sampled 0/1 output is piecewise-constant in the "
                    "probabilities; threshold crossings break the oracle",
    "binomial_op": "sampled counts, same threshold-crossing issue",
    "multinomial_op": "sampled integer categories",
    "paged_attention_pallas_op": "Pallas decode kernel: no VJP by design "
                                 "(serving decode runs under no-grad); "
                                 "forward parity vs the einsum oracle in "
                                 "test_pallas_attention.py",
    "prefill_attention_pallas_op": "Pallas prefill kernel: no VJP by design "
                                   "(a serving prefill runs under no-grad); "
                                   "forward parity vs the einsum oracle in "
                                   "test_pallas_attention.py",
    # --- higher-order callables, not tensor ops -------------------------
    "recompute": "takes a callable (checkpoint wrapper), not a tensor op",
    "spmd_pipeline": "pipeline schedule driver (callable + mesh), covered "
                     "by test_loss_parity/test_pipeline_interleaved",
    # --- distributed ops needing an initialized group/mesh --------------
    "parallel_cross_entropy": "needs a model-parallel group; covered by "
                              "test_loss_parity::mp2",
    "sharded_embedding_lookup": "needs a sharding mesh; covered by "
                                "test_loss_parity",
    "mp_wire_row_linear": "quantized mp recombination needs live mesh "
                          "axes; fwd+vjp covered by test_mp_comm.py",
    "mp_wire_col_linear": "same blocked-wire mesh requirement; vjp "
                          "covered by test_mp_comm.py",
    "mp_wire_vocab_embedding": "same blocked-wire mesh requirement; "
                               "grad covered by test_mp_comm.py",
    # --- numerically-hostile domains at f32 central differences ---------
    "spectral_norm_weight": "power-iteration fixed point: analytic grad "
                            "treats u/v as constants by design (reference "
                            "semantics), numeric diff sees the iteration",
    "pca_lowrank_helper": "randomized range finder (internal PRNG)",
    "svd_lowrank_op": "randomized algorithm (internal PRNG)",
    "lu_op": "pivoted factorization: pivot choice is discontinuous in the "
             "entries; value parity covered in test_linalg_special_extra",
    "lu_unpack": "consumes lu_op pivots (integer permutation decode)",
    "ormqr_op": "householder reflector application; f32 noise-dominated "
                "(value parity in test_linalg_special_extra)",
    "rnnt_loss_op": "alignment-lattice DP over integer labels; exact-grad "
                    "test lives in test_losses_extra.py",
    "llm_int8_linear": "straight-through estimator: analytic grad is the "
                       "float path BY DESIGN; numeric diff sees the int8 "
                       "rounding staircase (value parity in test_nn_quant)",
}

# ----------------------------------------------------------------------
# per-op input overrides (upstream OpTest's per-op setUp analogue);
# value: builder -> (args, kwargs), or a list of candidate builders.
# Signatures cited from the registered inner functions.
# ----------------------------------------------------------------------


def _conv_spec(nd):
    def build():
        x = _f((2, 4) + (6,) * nd)
        w = _f((4, 4) + (3,) * nd) - 0.6
        return ([x, w], {"stride": 1, "padding": 1})
    return build


OVERRIDES = {
    # ---- linalg with structured operands -------------------------------
    "cholesky": lambda: ([_spd(3)], {}),
    "cholesky_solve": lambda: (
        [_f((3, 2)), np.linalg.cholesky(_spd(3)).astype(np.float32)], {}),
    "inverse": lambda: ([_spd(3)], {}),
    "pinv": lambda: ([_f((3, 3))], {}),
    "solve": lambda: ([_spd(3), _f((3, 2))], {}),
    "triangular_solve": lambda: (
        [np.tril(_spd(3)).astype(np.float32), _f((3, 2))], {}),
    "slogdet": lambda: ([_spd(3)], {}),
    "det": lambda: ([_spd(3)], {}),
    "matrix_exp": lambda: ([_f((3, 3)) * 0.3], {}),
    # domain-tailored inputs that replace former skip-table entries: well
    # inside each op's smooth region so f32 central differences are valid
    "matrix_power": lambda: ([_spd(3) * 0.5, 2], {}),
    "frexp": lambda: ([_f((3, 4), lo=2.2, hi=3.8)], {}),
    "householder_product": lambda: ([_f((4, 2)) * 0.1, _f((2,)) * 0.1],
                                    {}),
    "multigammaln": lambda: ([_f((3, 4)) + 3.0, 2], {}),
    "lgamma": lambda: ([_f((3, 4)) + 2.0], {}),
    "polygamma": lambda: ([_f((3, 4)) + 2.0, 1], {}),
    "logit": lambda: ([_f((3, 4), lo=0.3, hi=0.7)], {}),
    "qr_op": lambda: ([_f((4, 3))], {"mode": "reduced"}),
    "svd_op": lambda: ([_f((4, 3))], {"full_matrices": False}),
    "svdvals": lambda: ([_f((4, 3))], {}),
    "norm_op": lambda: ([_f((3, 4)), 2, None, False], {}),
    "matrix_norm_op": lambda: ([_f((3, 4)), "fro", (-2, -1), False], {}),
    "matrix_rank_op": lambda: ([_spd(3), None, False], {}),
    "multi_dot_op": lambda: ([[_f((3, 4)), _f((4, 2)), _f((2, 3))]], {}),
    "lstsq_op": lambda: ([_f((4, 3)), _f((4, 2)), None], {}),
    "cond_op": lambda: ([_spd(3), 2], {}),
    "vander_op": lambda: ([_f((4,)), 3, False], {}),
    "tensordot_op": lambda: ([_f((3, 4)), _f((4, 2)), 1], {}),
    "bilinear": lambda: ([_f((3, 4)), _f((3, 5)), _f((2, 4, 5))], {}),
    "einsum_op": lambda: ([[_f((3, 4)), _f((4, 2))], "ij,jk->ik"], {}),
    # ---- indexing / scatter-gather -------------------------------------
    "take_along_axis": lambda: (
        [_f((3, 4)), _ids((3, 2), 4)], {"axis": 1}),
    "put_along_axis": lambda: (
        [_f((3, 4)), _ids((3, 2), 4), _f((3, 2))], {"axis": 1}),
    "take_op": lambda: ([_f((3, 4)), _ids((5,), 12)], {"mode": "raise"}),
    "scatter_op": lambda: ([_f((4, 3)), _ids((2,), 4), _f((2, 3))], {}),
    "scatter_nd": lambda: ([_ids((3, 1), 4), _f((3, 2)), (4, 2)], {}),
    "scatter_nd_add": lambda: (
        [_f((4, 2)), _ids((3, 1), 4), _f((3, 2))], {}),
    "index_select_op": lambda: ([_f((3, 4)), _ids((2,), 3)], {"axis": 0}),
    "index_add_op": lambda: (
        [_f((3, 4)), _ids((2,), 3), _f((2, 4))], {"axis": 0}),
    "index_put_op": lambda: (
        [_f((3, 4)), (_ids((2,), 3),), _f((2, 4))], {}),
    "index_fill_op": lambda: ([_f((3, 4)), _ids((2,), 3), 0, 0.3], {}),
    "index_sample": lambda: ([_f((3, 4)), _ids((3, 2), 4)], {}),
    "masked_scatter": lambda: (
        [_f((3, 4)), _rng.random((3, 4)) > 0.5, _f((12,))], {}),
    "masked_fill_op": lambda: (
        [_f((3, 4)), _rng.random((3, 4)) > 0.5, 0.3], {}),
    "masked_select": lambda: ([_f((3, 4)), _rng.random((3, 4)) > 0.5], {}),
    "gather_nd_op": lambda: ([_f((3, 4)), _ids((2, 1), 3)], {}),
    "gather_op": lambda: ([_f((3, 4)), _ids((2,), 3)], {"axis": 0}),
    "setitem_op": lambda: ([_f((3, 4)), _f((2, 4)), (slice(0, 2),)], {}),
    "getitem_op": lambda: ([_f((3, 4)), (slice(0, 2),)], {}),
    "select_scatter": lambda: (
        [_f((3, 4)), _f((4,))], {"axis": 0, "index": 1}),
    "slice_scatter": lambda: (
        [_f((3, 4)), _f((2, 4))],
        {"axes": [0], "starts": [0], "ends": [2], "strides": [1]}),
    "sp_scatter": lambda: ([_f((2, 3, 4)), 1], {}),
    "segment_sum_op": lambda: ([_f((4, 3)), _ids((4,), 2), 2], {}),
    "segment_mean_op": lambda: ([_f((4, 3)), _ids((4,), 2), 2], {}),
    "segment_max_op": lambda: ([_fsep((4, 3)), _ids((4,), 2), 2], {}),
    "segment_min_op": lambda: ([_fsep((4, 3)), _ids((4,), 2), 2], {}),
    "send_u_recv_op": lambda: (
        [_f((4, 3)), _ids((5,), 4), _ids((5,), 4), "sum", 4], {}),
    "send_ue_recv_op": lambda: (
        [_f((4, 3)), _f((5, 3)), _ids((5,), 4), _ids((5,), 4), "add",
         "sum", 4], {}),
    "send_uv_op": lambda: (
        [_f((4, 3)), _f((4, 3)), _ids((5,), 4), _ids((5,), 4), "add"], {}),
    "bincount_op": lambda: ([_ids((6,), 4), _f((6,)), 0], {}),
    "multiplex": lambda: ([[_f((3, 4)), _f((3, 4))], _ids((3,), 2)], {}),
    "moveaxis": lambda: ([_f((2, 3, 4)), 0, 2], {}),
    # ---- shape / layout -------------------------------------------------
    "unflatten": lambda: ([_f((3, 4)), 1, (2, 2)], {}),
    "squeeze_op": lambda: ([_f((3, 1, 4))], {"axis": (1,)}),
    "unsqueeze_op": lambda: ([_f((3, 4))], {"axis": (1,)}),
    "split_op": lambda: ([_f((4, 3)), 2], {"axis": 0}),
    "sort_op": lambda: ([_fsep((3, 4)), -1, False], {}),
    "argsort_op": lambda: ([_fsep((3, 4)), -1, False], {}),
    "argmax_op": lambda: ([_fsep((3, 4)), 0, False], {}),
    "argmin_op": lambda: ([_fsep((3, 4)), 0, False], {}),
    "topk_op": lambda: ([_fsep((3, 8)), 2, -1, True, True], {}),
    "kthvalue_op": lambda: ([_fsep((3, 8)), 2, -1, False], {}),
    "mode_op": lambda: ([_ids((3, 8), 3).astype(np.float32)], {}),
    "unfold_op": lambda: ([_f((8,)), 0, 4, 2], {}),
    "unfold": lambda: ([_f((2, 3, 8, 8)), 2], {}),
    "fold_op": lambda: (
        [_f((2, 12, 9)), (5, 5), 2], {}),
    "slice_op": lambda: (
        [_f((3, 4))], {"axes": [0], "starts": [0], "ends": [2]}),
    "strided_slice": lambda: (
        [_f((4, 4))],
        {"axes": [0], "starts": [0], "ends": [4], "strides": [2]}),
    "pad_nd": lambda: ([_f((3, 4)), [1, 1]], {}),
    "pad_op": lambda: ([_f((2, 3, 4)), [1, 1], "constant", 0.0], {}),
    "roll_op": lambda: ([_f((3, 4)), 1], {"axis": 0}),
    "flip_op": lambda: ([_f((3, 4))], {"axis": 0}),
    "tile_op": lambda: ([_f((3, 4)), (2, 1)], {}),
    "broadcast_to_op": lambda: ([_f((1, 4)), (3, 4)], {}),
    "expand_as_op": lambda: ([_f((1, 4)), _f((3, 4))], {}),
    "as_strided_op": lambda: ([_f((12,)), (3, 2), (4, 1)], {}),
    "view_op": lambda: ([_f((3, 4)), (4, 3)], {}),
    "diagonal_scatter": lambda: ([_f((3, 3)), _f((3,))], {}),
    "fill_diagonal_tensor": lambda: ([_f((3, 3)), _f((3,))], {}),
    "crop": lambda: ([_f((3, 4))], {"shape": (2, 2), "offsets": (0, 1)}),
    "pixel_shuffle_op": lambda: ([_f((2, 4, 3, 3)), 2, "NCHW"], {}),
    "pixel_unshuffle_op": lambda: ([_f((2, 1, 4, 4)), 2, "NCHW"], {}),
    "channel_shuffle": lambda: ([_f((2, 4, 3, 3)), 2], {}),
    "temporal_shift": lambda: (
        [_f((4, 4, 3, 3))], {"seg_num": 2, "shift_ratio": 0.25}),
    "cast_op": lambda: ([_f((3, 4)), "float32"], {}),
    # ---- signal ---------------------------------------------------------
    "frame_op": lambda: ([_f((2, 16)), 4, 2], {}),
    "overlap_add_op": lambda: ([_f((2, 4, 5)), 2], {}),
    # stft/istft: complex outputs -> land in non_float via the checker
    "stft_op": lambda: ([_f((2, 16)), 8], {"hop_length": 4}),
    "istft_op": lambda: (
        [np.stack([_f((5, 3)), _f((5, 3))], -1).view(np.complex64)
         .squeeze(-1).astype(np.complex64), 8],
        {"hop_length": 4, "length": 16}),
    # ---- nn: conv / pool / norm / attention -----------------------------
    "conv1d": _conv_spec(1),
    "conv2d": _conv_spec(2),
    "conv3d": _conv_spec(3),
    "conv1d_transpose": lambda: (
        [_f((2, 4, 6)), _f((4, 3, 3)) - 0.6], {"stride": 1, "padding": 1}),
    "conv2d_transpose": lambda: (
        [_f((2, 4, 6, 6)), _f((4, 3, 3, 3)) - 0.6],
        {"stride": 1, "padding": 1}),
    "conv3d_transpose": lambda: (
        [_f((2, 4, 5, 5, 5)), _f((4, 3, 3, 3, 3)) - 0.6],
        {"stride": 1, "padding": 1}),
    "max_pool1d": lambda: ([_fsep((2, 3, 8)), 2], {}),
    "max_pool2d": lambda: ([_fsep((2, 3, 8, 8)), 2], {}),
    "max_pool3d": lambda: ([_fsep((2, 3, 6, 6, 6)), 2], {}),
    "avg_pool1d": lambda: ([_f((2, 3, 8)), 2], {}),
    "avg_pool2d": lambda: ([_f((2, 3, 8, 8)), 2], {}),
    "avg_pool3d": lambda: ([_f((2, 3, 6, 6, 6)), 2], {}),
    "adaptive_avg_pool1d": lambda: ([_f((2, 3, 8)), 2], {}),
    "adaptive_avg_pool2d": lambda: ([_f((2, 3, 8, 8)), 2], {}),
    "adaptive_avg_pool3d": lambda: ([_f((2, 3, 6, 6, 6)), 2], {}),
    "adaptive_max_pool1d": lambda: ([_fsep((2, 3, 8)), 2], {}),
    "adaptive_max_pool2d": lambda: ([_fsep((2, 3, 8, 8)), 2], {}),
    "adaptive_max_pool3d": lambda: ([_fsep((2, 3, 6, 6, 6)), 2], {}),
    "max_unpool1d": lambda: (
        [_fsep((2, 3, 4)), np.tile(_ids((1, 1, 4), 8), (2, 3, 1)), 2], {}),
    "max_unpool2d": lambda: (
        [_fsep((2, 3, 4, 4)),
         np.tile(_ids((1, 1, 4, 4), 4), (2, 3, 1, 1)), 2], {}),
    "max_unpool3d": lambda: (
        [_fsep((2, 3, 3, 3, 3)),
         np.tile(_ids((1, 1, 3, 3, 3), 8), (2, 3, 1, 1, 1)), 2], {}),
    "lp_pool1d": lambda: ([_f((2, 3, 8)), 2.0, 2], {}),
    "lp_pool2d": lambda: ([_f((2, 3, 8, 8)), 2.0, 2], {}),
    "maxout": lambda: ([_fsep((2, 4, 3)), 2], {}),
    "lrn_op": lambda: ([_f((2, 4, 3, 3)), 5, 1e-4, 0.75, 1.0], {}),
    "interpolate_op": lambda: (
        [_f((2, 3, 4, 4)), (8, 8), "nearest", False, "NCHW"], {}),
    "grid_sample_op": lambda: (
        [_f((2, 3, 4, 4)), _f((2, 4, 4, 2)) - 0.6, "bilinear", "zeros",
         True], {}),
    "affine_grid": lambda: ([_f((2, 2, 3)), (2, 3, 4, 4)], {}),
    "affine_grid_op": lambda: ([_f((2, 2, 3)), (2, 3, 4, 4)], {}),
    "prelu": lambda: ([_f((2, 3, 4)), _f((3,))], {}),
    "rms_norm_op": lambda: ([_f((3, 4)), _f((4,)), 1e-5, 1], {}),
    "layer_norm_op": lambda: (
        [_f((3, 4)), _f((4,)), _f((4,)), 1e-5, 1], {}),
    "instance_norm_op": lambda: (
        [_f((2, 3, 4, 4)), _f((3,)), _f((3,)), 1e-5], {}),
    "group_norm_op": lambda: (
        [_f((2, 4, 3, 3)), _f((4,)), _f((4,)), 1e-5, 2, "NCHW"], {}),
    "embedding": lambda: ([_ids((3, 2), 5), _f((5, 4))], {}),
    "embedding_op": lambda: ([_ids((3, 2), 5), _f((5, 4))], {}),
    "one_hot_op": lambda: ([_ids((3,), 5), 5], {}),
    "rnn_forward_op": [
        lambda: ([_f((2, 3, 4)), np.zeros((1, 2, 3), np.float32),
                  np.zeros((1, 2, 3), np.float32),
                  [_f((9, 4)), _f((9, 3)), _f((9,)), _f((9,))],
                  "GRU", 1, 1, False, True], {}),
    ],
    # attention family (shapes mirror tests/test_attention_kernels.py)
    "sdpa_op": lambda: (
        [_f((2, 4, 2, 8)), _f((2, 4, 2, 8)), _f((2, 4, 2, 8)), None,
         _key(), 0.0, False, None, False], {}),
    "gqa_flash_attention": lambda: (
        [_f((1, 4, 2, 8)), _f((1, 4, 1, 8)), _f((1, 4, 1, 8))],
        {"causal": True}),
    "block_causal_attention": lambda: (
        [_f((1, 4, 2, 8)), _f((1, 4, 1, 8)), _f((1, 4, 1, 8)), 2], {}),
    "flash_attn_unpadded_op": lambda: (
        [_f((6, 2, 8)), _f((6, 2, 8)), _f((6, 2, 8)),
         np.array([0, 3, 6], np.int32), np.array([0, 3, 6], np.int32),
         0.35, False], {}),
    "sparse_attention_op": lambda: (
        [_f((1, 2, 4, 4)), _f((1, 2, 4, 4)), _f((1, 2, 4, 4)),
         np.tile(np.array([0, 2, 4, 6, 8], np.int32), (1, 2, 1)),
         np.tile(np.array([0, 1, 1, 2, 2, 3, 3, 0], np.int32), (1, 2, 1)),
         None, None], {}),
    "cache_write": lambda: (
        [_f((2, 8, 2, 4)), _f((2, 1, 2, 4)), 3], {}),
    "apply_rope": lambda: (
        [_f((2, 4, 2, 8)), _f((4, 4)), _f((4, 4))], {}),
    "rope_at": lambda: (
        [_f((2, 1, 2, 8)), _f((16, 4)), _f((16, 4)), 3], {}),
    "rope_positions": lambda: (
        [_f((2, 3, 2, 8)), _f((16, 4)), _f((16, 4)),
         np.array([3, 0, 7], np.int32)], {}),
    "decode_attention_op": lambda: (
        [_f((2, 1, 4, 8)), _f((2, 2, 8, 8)), _f((2, 2, 8, 8)),
         np.array([3, 5], np.int32), 0.35], {}),
    # tiny shapes on purpose: numeric grad cost scales with element count
    "paged_attention_op": lambda: (
        [_f((1, 1, 2, 4)), _f((3, 1, 4, 4)), _f((3, 1, 4, 4)), None, None,
         np.array([[1, 2]], np.int32),
         np.array([5], np.int32), 0.35], {}),
    # ---- dropout family: deterministic given a fixed PRNG key ----------
    "dropout_op": lambda: ([_f((3, 4)), _key(), 0.4, "upscale_in_train"],
                           {}),
    "dropout_axis_op": lambda: (
        [_f((3, 4)), _key(), 0.4, (0,), "upscale_in_train"], {}),
    "alpha_dropout_op": lambda: ([_f((3, 4)), _key(), 0.4], {}),
    "feature_alpha_dropout_op": lambda: ([_f((2, 3, 4)), _key(), 0.4], {}),
    # ---- samplers: deterministic given key; no diff inputs -------------
    "normal_op": lambda: ([_key(), (3, 4), "float32", 0.0, 1.0], {}),
    "normal_tensor_op": lambda: (
        [_f((3, 4)), _f((3, 4)) + 0.5, _key(), (3, 4)], {}),
    "uniform_op": lambda: ([_key(), (3, 4), "float32", 0.0, 1.0], {}),
    "log_normal_op": lambda: ([_key(), (3, 4), 0.0, 1.0, "float32"], {}),
    "randint_op": lambda: ([_key(), (3, 4), 0, 5, "int32"], {}),
    "randperm_op": lambda: ([_key(), 5, "int32"], {}),
    "standard_gamma_op": lambda: ([_f((3, 4)) + 1.0, _key()], {}),
    "poisson_op": lambda: ([_f((3, 4)) * 4, _key()], {}),
    # ---- losses ---------------------------------------------------------
    "cross_entropy_op": lambda: (
        [_f((3, 5)), _ids((3,), 5), None, -100, "mean", False, -1, 0.0],
        {}),
    "nll_loss_op": lambda: (
        [np.log(_f((3, 5))), _ids((3,), 5)], {}),
    "nll_from_logp": lambda: (
        [np.log(_f((3, 5))), _ids((3,), 5), None, -100, "mean", False, -1],
        {}),
    "softmax_with_cross_entropy": lambda: (
        [_f((3, 5)), _ids((3, 1), 5)], {}),
    "margin_cross_entropy_op": lambda: (
        [_f((3, 5)), _ids((3,), 5), 1.0, 0.5, 0.0, 8.0, "mean", False],
        {}),
    "multi_margin_loss_op": lambda: (
        [_f((3, 5)), _ids((3,), 5), 1, 1.0, None, "mean"], {}),
    "multi_label_margin_loss_op": lambda: (
        [_f((3, 5)), _ids((3, 5), 5)], {}),
    "multi_label_soft_margin_loss": lambda: (
        [_f((3, 5)), _ids((3, 5), 2).astype(np.float32)], {}),
    "soft_margin_loss": lambda: (
        [_f((3, 5)), (_ids((3, 5), 2) * 2 - 1).astype(np.float32)], {}),
    "margin_ranking_op": lambda: (
        [_f((3,)), _f((3,)), (_ids((3,), 2) * 2 - 1).astype(np.int32),
         0.1, "mean"], {}),
    "hinge_embedding_op": lambda: (
        [_f((3, 4)), (_ids((3, 4), 2) * 2 - 1).astype(np.int32), 1.0,
         "mean"], {}),
    "cosine_embedding_op": lambda: (
        [_f((3, 4)), _f((3, 4)),
         (_ids((3,), 2) * 2 - 1).astype(np.int32), 0.1, "mean"], {}),
    "npair_loss_op": lambda: (
        [_f((3, 4)), _f((3, 4)), _ids((3,), 3), 0.002], {}),
    "triplet_margin_op": lambda: (
        [_f((3, 4)), _f((3, 4)), _f((3, 4)), 1.0, 2.0, 1e-6, False,
         "mean"], {}),
    "triplet_margin_with_distance_op": lambda: (
        [_f((3, 4)), _f((3, 4)), _f((3, 4))], {}),
    "ctc_loss_op": lambda: (
        [_f((6, 2, 5)), _ids((2, 3), 4) + 1,
         np.array([6, 6], np.int32), np.array([3, 3], np.int32), 0,
         "mean"], {}),
    "hsigmoid_loss_op": lambda: _hsigmoid_spec(),
    "adaptive_log_softmax_op": lambda: (
        [_f((3, 8)), _ids((3,), 10), _f((8, 6)),
         [[_f((8, 2)), _f((2, 5))]], _f((6,)), (5, 10)], {}),
    "dice_loss": lambda: ([_f((3, 4, 5)), _ids((3, 4, 1), 5)], {}),
    "dice_loss_op": lambda: ([_f((3, 4, 5)), _ids((3, 4, 1), 5)], {}),
    "sigmoid_focal_loss": lambda: (
        [_f((3, 5)), _ids((3, 5), 2).astype(np.float32)], {}),
    "sigmoid_focal_loss_op": lambda: (
        [_f((3, 5)), _ids((3, 5), 2).astype(np.float32)], {}),
    "bce_op": lambda: (
        [_f((3, 4)), _ids((3, 4), 2).astype(np.float32), None, "mean"],
        {}),
    "bce_logits_op": lambda: (
        [_f((3, 4)), _ids((3, 4), 2).astype(np.float32), None, None,
         "mean"], {}),
    "kl_div_op": lambda: (
        [np.log(_f((3, 4))), _f((3, 4)), "mean", False], {}),
    "mse_loss_op": lambda: ([_f((3, 4)), _f((3, 4)), "mean"], {}),
    "l1_loss_op": lambda: ([_f((3, 4)), _f((3, 4)), "mean"], {}),
    "smooth_l1_op": lambda: ([_f((3, 4)), _f((3, 4)), "mean", 1.0], {}),
    "huber_op": lambda: ([_f((3, 4)), _f((3, 4)), "mean", 1.0], {}),
    "log_loss": lambda: ([_f((3, 4)), _ids((3, 4), 2).astype(np.float32)],
                         {}),
    "gaussian_nll_loss": lambda: (
        [_f((3, 4)), _f((3, 4)), _f((3, 4)) + 0.5], {}),
    "poisson_nll_loss": lambda: ([_f((3, 4)), _f((3, 4)) * 3], {}),
    "label_smooth_op": lambda: ([_f((3, 5)), None, 0.1], {}),
    # ---- moe / experts --------------------------------------------------
    "moe_gate_dispatch": lambda: (
        [_f((6, 3)), _key(), 2, 4, False], {}),
    "moe_apply": lambda: (
        [_f((6, 4)), _f((6, 3, 2)), _ids((6, 3, 2), 2).astype(np.float32),
         _f((3, 4, 8)), _f((3, 1, 8)), _f((3, 8, 4)), _f((3, 1, 4)),
         jax.nn.gelu], {}),
    "moe_apply_dropless": lambda: (
        [_f((6, 4)), _f((6, 3)), _f((3, 4, 8)), _f((3, 1, 8)),
         _f((3, 8, 4)), _f((3, 1, 4)), jax.nn.gelu, 2], {}),
    "moe_routed_experts": lambda: (
        [_f((6, 4)), _f((4, 3)), _f((3, 4, 8)), _f((3, 4, 4)), 2], {}),
    "fused_ec_moe_op": lambda: (
        [_f((2, 3, 4)), _f((2, 3, 3)), _f((3, 4, 8)), _f((3, 1, 8)),
         _f((3, 8, 4)), _f((3, 1, 4)), "gelu", 3], {}),
    # ---- misc ----------------------------------------------------------
    "sequence_mask_op": lambda: ([_ids((3,), 4) + 1, 5, "float32"], {}),
    "quantile_op": lambda: ([_f((3, 8)), 0.5, 1, False], {}),
    "nanquantile_op": lambda: ([_f((3, 8)), 0.5, 1, False], {}),
    "allclose_op": lambda: ([_f((3, 4)), _f((3, 4)), 1e-5, 1e-8, False],
                            {}),
    "isclose_op": lambda: ([_f((3, 4)), _f((3, 4)), 1e-5, 1e-8, False],
                           {}),
    "bitwise_and": lambda: ([_ids((3, 4), 8), _ids((3, 4), 8)], {}),
    "bitwise_or": lambda: ([_ids((3, 4), 8), _ids((3, 4), 8)], {}),
    "bitwise_xor": lambda: ([_ids((3, 4), 8), _ids((3, 4), 8)], {}),
    "bitwise_not": lambda: ([_ids((3, 4), 8)], {}),
    "bitwise_left_shift": lambda: ([_ids((3, 4), 8), _ids((3, 4), 3)], {}),
    "bitwise_right_shift": lambda: ([_ids((3, 4), 8), _ids((3, 4), 3)],
                                    {}),
    "gcd": lambda: ([_ids((3, 4), 12) + 1, _ids((3, 4), 12) + 1], {}),
    "lcm": lambda: ([_ids((3, 4), 12) + 1, _ids((3, 4), 12) + 1], {}),
    "fake_quantize_dequantize_abs_max": lambda: (
        [_f((3, 4))], {"scale": np.float32(1.0).reshape(())}),
    "softmax_mask_fuse_op": lambda: (
        [_f((2, 2, 3, 3)), _f((2, 1, 3, 3))], {}),
    "batch_norm_infer": lambda: (
        [_f((2, 3, 4, 4)), _f((3,)), _f((3,)) + 0.5, _f((3,)), _f((3,)),
         1e-5, "NCHW"], {}),
    "bincount": lambda: ([_ids((6,), 4)], {"weights": _f((6,))}),
    "flatten_op": lambda: ([_f((2, 3, 4)), 0, 1], {}),
    "lerp": lambda: ([_f((3, 4)), _f((3, 4)), 0.3], {}),
    "linear": lambda: ([_f((3, 4)), _f((4, 2)), _f((2,))], {}),
    "masked_fill": lambda: (
        [_f((3, 4)), _rng.random((3, 4)) > 0.5, 0.3], {}),
}


def _hsigmoid_spec():
    from paddle_tpu.nn.functional.loss import _default_tree_paths

    table, code, mask = _default_tree_paths(5)
    return ([_f((3, 4)), _ids((3,), 5), _f((4, 4)), _f((4,)),
             table.astype(np.int32), code.astype(np.float32),
             mask.astype(np.float32)], {})


def _is_float_dtype(dt) -> bool:
    s = str(dt)
    return "float" in s and "complex" not in s


# ----------------------------------------------------------------------
# generic signature-driven synthesis (the default path)
# ----------------------------------------------------------------------
_SCALAR_PARAMS = {
    "axis": 0, "dim": 0, "axes": (0,), "num_rows": 3, "num_columns": 3,
    "offset": 0, "k": 1, "diagonal": 0, "n": 2, "num": 3, "decimals": 1,
    "num_classes": 5, "depth": 5, "bins": 4, "nbins": 4, "seed": 0,
    "shape": (3, 4), "perm": (1, 0), "repeat_times": (2, 1), "repeats": 2,
    "num_or_sections": 2, "start": 0, "stop": 2, "step": 1,
    "eps": 1e-5, "epsilon": 1e-5, "alpha": 0.9, "beta": 0.9,
    "min": 0.1, "max": 0.9, "threshold": 0.5, "value": 0.5, "scale": 1.2,
    "rcond": 1e-6, "tol": 1e-6, "lambd": 0.4, "negative_slope": 0.1,
    "p": 2.0, "q": 0.5, "t_min": 0.1, "t_max": 0.9,
    "lower": 0.1, "upper": 0.9, "rtol": 1e-5, "atol": 1e-8,
    "keepdim": False, "descending": False, "largest": True, "sorted": True,
    "equal_nan": False, "return_mask": False, "ceil_mode": False,
    "align_corners": False, "hermitian": False, "increasing": False,
    "time_major": False, "has_bias": True, "soft_label": False,
    "log_target": False, "full": False, "replacement": True,
    "use_aux_noise": False, "causal": False, "use_pallas": False,
    "swap": False, "reduction": "mean", "data_format": "NCHW",
    "dtype": "float32", "mode": "constant", "ignore_index": -100,
    "label_smoothing": 0.0, "delta": 1.0, "margin": 0.1, "blank": 0,
    "exclusive": True, "reverse": False, "dropout_p": 0.0,
    "fastemit_lambda": 0.0, "padding_idx": None, "weight": None,
    "bias": None, "pos_weight": None, "prior_dist": None,
    "normalizer": None, "window": None, "key_padding_mask": None,
    "attn_mask": None, "mask": None, "size": 2, "groups": 2,
    "kernel_size": 2, "stride": None, "padding": 0, "output_size": 2,
    "num_layers": 1, "ndirs": 1, "num_experts": 2, "top_k": 2,
    "capacity": 4, "act": "gelu", "msg": "add", "pool": "sum",
    "begin_axis": -1, "l2_reg": 0.002, "maxlen": 5, "cutoffs": (5, 10),
    "num_samples": 3, "low": 0, "high": 5, "mean": 0.0, "std": 1.0,
}
_INT_TENSOR_PARAMS = {"index", "indices", "ids", "segment_ids",
                      "src_index", "dst_index", "src", "dst", "pos",
                      "lengths", "label_lengths", "input_lengths",
                      "logit_lengths", "cu_q", "cu_k"}
_BOOL_TENSOR_PARAMS = {"condition"}
_LIST_TENSOR_PARAMS = {"xs", "inputs", "tensors", "arrays", "mats",
                       "operands", "flat_weights", "tail_weights"}
_KEY_PARAMS = {"key"}
# labels: tried both as int class-ids and float same-shape targets
_LABEL_PARAMS = {"label", "labels", "target"}


def _generic_specs(name):
    """Yield candidate (args, kwargs) specs from the op's signature."""
    op = OP_REGISTRY[name]
    sig = inspect.signature(op)
    required = []
    for p in sig.parameters.values():
        if p.kind in (p.VAR_KEYWORD, p.VAR_POSITIONAL):
            continue
        if p.default is inspect.Parameter.empty:
            required.append(p)
    if not required:
        raise ValueError("no required params to synthesize")

    shapes = [(3, 4), (3, 3), "spd", (4,), (2, 3, 4)]
    for shp in shapes:
        for label_mode in ("float_like", "class_ids"):
            kwargs = {}
            for p in required:
                lname = p.name.lower()
                if lname in _LIST_TENSOR_PARAMS:
                    kwargs[p.name] = [_mk_shape(shp) for _ in range(2)]
                elif lname in _KEY_PARAMS:
                    kwargs[p.name] = _key()
                elif lname in _LABEL_PARAMS:
                    kwargs[p.name] = (_mk_shape(shp)
                                      if label_mode == "float_like"
                                      else _ids((3,), 3))
                elif lname in _INT_TENSOR_PARAMS:
                    kwargs[p.name] = _ids((2,), 3)
                elif lname in _BOOL_TENSOR_PARAMS:
                    kwargs[p.name] = _rng.random((3, 4)) > 0.5
                elif lname in _SCALAR_PARAMS:
                    kwargs[p.name] = _SCALAR_PARAMS[lname]
                else:
                    kwargs[p.name] = _mk_shape(shp)
            yield [], kwargs
            if not any(p.name.lower() in _LABEL_PARAMS for p in required):
                break  # label variants identical; skip the duplicate


def _mk_shape(shp):
    if shp == "spd":
        return _spd(3)
    return _f(shp)


def candidate_specs(name):
    ov = OVERRIDES.get(name)
    if ov is not None:
        for builder in (ov if isinstance(ov, list) else [ov]):
            yield builder()
        return
    yield from _generic_specs(name)


# ----------------------------------------------------------------------
# spec plumbing: numpy leaves <-> tensors, perturbation, flattening
# ----------------------------------------------------------------------
def _map_leaves(obj, fn):
    if isinstance(obj, np.ndarray):
        return fn(obj)
    if isinstance(obj, list):
        return [_map_leaves(o, fn) for o in obj]
    if isinstance(obj, tuple):
        return tuple(_map_leaves(o, fn) for o in obj)
    if isinstance(obj, dict):
        return {k: _map_leaves(v, fn) for k, v in obj.items()}
    return obj


def _float_leaves(spec):
    out = []

    def visit(a):
        if a.dtype == np.float32:
            out.append(a)
        return a

    _map_leaves(spec, visit)
    return out


def _to_tensors(spec):
    def conv(a):
        if a.dtype == np.float32:
            return paddle.to_tensor(a, stop_gradient=False)
        return paddle.to_tensor(a)

    return _map_leaves(spec, conv)


def _perturb(spec, deltas, eps):
    it = iter(deltas)

    def conv(a):
        if a.dtype == np.float32:
            return (a + eps * next(it)).astype(np.float32)
        return a

    return _map_leaves(spec, conv)


def _flatten_out(out):
    if isinstance(out, (list, tuple)):
        r = []
        for o in out:
            r.extend(_flatten_out(o))
        return r
    if isinstance(out, dict):
        r = []
        for o in out.values():
            r.extend(_flatten_out(o))
        return r
    return [out]


def _input_tensors(args_kw):
    out = []

    def walk(obj):
        if isinstance(obj, paddle.Tensor):
            if not obj.stop_gradient:
                out.append(obj)
        elif isinstance(obj, (list, tuple)):
            for o in obj:
                walk(o)
        elif isinstance(obj, dict):
            for o in obj.values():
                walk(o)

    args, kwargs = args_kw
    walk(args)
    walk(kwargs)
    return out


def _forward_scalar(name, spec, weights=None):
    args, kwargs = _to_tensors(spec)
    out = OP_REGISTRY[name](*args, **kwargs)
    leaves = [o for o in _flatten_out(out) if isinstance(o, paddle.Tensor)]
    fouts = [o for o in leaves if _is_float_dtype(o.dtype)]
    if not fouts:
        return None, (args, kwargs), weights
    if weights is None:
        weights = [_rng.standard_normal(tuple(o.shape)).astype(np.float32)
                   if len(tuple(o.shape)) else
                   np.float32(_rng.standard_normal()) for o in fouts]
    scalar = None
    for o, w in zip(fouts, weights):
        term = (o.astype("float32") * paddle.to_tensor(w)).sum()
        scalar = term if scalar is None else scalar + term
    return scalar, (args, kwargs), weights


# ----------------------------------------------------------------------
# the check itself
# ----------------------------------------------------------------------
def check_op_gradient(name, rtol=5e-2, atol=5e-2):
    """'checked' | 'non_float' | 'stochastic', or raises on failure."""
    import zlib

    from paddle_tpu.distributed import mesh as _mesh_mod

    global _rng
    # per-op reseed (stable hash): results do not depend on which ops ran
    # before, or on PYTHONHASHSEED
    _rng = np.random.default_rng(zlib.crc32(name.encode()) + 7)
    # neutralize distributed state left by earlier tests: mesh-aware ops
    # (mp_reshard, moe dispatch, ...) must classify single-device here,
    # whatever ran before in the same pytest process
    prev_mesh = _mesh_mod.get_global_mesh()
    _mesh_mod.set_global_mesh(None)
    try:
        return _check_op_gradient_inner(name, rtol, atol)
    finally:
        _mesh_mod.set_global_mesh(prev_mesh)


def _check_op_gradient_inner(name, rtol, atol):
    err = None
    saw_non_float = False
    for spec in candidate_specs(name):
        try:
            with paddle.no_grad():
                s0, _, w = _forward_scalar(name, spec)
        except Exception as e:
            err = e
            continue
        if s0 is None:
            saw_non_float = True
            continue
        if not np.isfinite(float(s0.numpy())):
            err = ValueError("non-finite forward")
            continue
        with paddle.no_grad():
            s1, _, _ = _forward_scalar(name, spec, weights=w)
        if float(s0.numpy()) != float(s1.numpy()):
            return "stochastic"
        return _grad_check(name, spec, rtol, atol)
    if saw_non_float:
        return "non_float"
    raise ValueError(
        f"input synthesis failed for {name!r}: "
        f"{type(err).__name__}: {err}")


def _grad_check(name, spec, rtol, atol):
    scalar, args_kw, weights = _forward_scalar(name, spec)
    ins = _input_tensors(args_kw)
    floats = _float_leaves(spec)
    assert len(ins) == len(floats), (
        f"{name}: float-leaf/tensor mismatch ({len(floats)} leaves, "
        f"{len(ins)} diff tensors)")
    if ins:
        scalar.backward()
    grads = [t.grad.numpy() if t.grad is not None
             else np.zeros(tuple(t.shape), np.float32) for t in ins]

    deltas = [_rng.standard_normal(g.shape).astype(np.float32)
              for g in grads]
    analytic = float(sum((g.astype(np.float64) * d).sum()
                         for g, d in zip(grads, deltas)))

    def at(eps):
        pert = _perturb(spec, deltas, eps)
        with paddle.no_grad():
            s, _, _ = _forward_scalar(name, pert, weights=weights)
        return float(s.numpy())

    last = None
    for eps in (1e-2, 3e-3, 3e-2):
        numeric = (at(eps) - at(-eps)) / (2 * eps)
        gap = abs(analytic - numeric)
        tol = atol + rtol * max(1.0, abs(numeric), abs(analytic))
        if gap <= tol:
            return "checked"
        last = (analytic, numeric, gap, tol, eps)
    a, n, gap, tol, eps = last
    raise AssertionError(
        f"{name}: analytic {a:.6g} vs numeric {n:.6g} "
        f"(gap {gap:.3g} > tol {tol:.3g}, eps {eps})")


def classify_all(names=None):
    """Classify `names` (default: the registry as of THIS call). Callers
    that parametrize over a collection-time snapshot should pass it —
    tests elsewhere in a session may register ad-hoc ops (e.g.
    test_loss_parity's cp_attn_test) that have no parametrized case."""
    out = {}
    for name in (sorted(OP_REGISTRY) if names is None else names):
        if name in SKIP:
            out[name] = f"skipped: {SKIP[name]}"
            continue
        try:
            out[name] = check_op_gradient(name)
        except AssertionError as e:
            out[name] = f"GRAD_FAIL: {e}"
        except Exception as e:
            out[name] = f"SYNTH_FAIL: {type(e).__name__}: {e}"
    return out


if __name__ == "__main__":
    import collections
    import sys

    res = classify_all()
    counts = collections.Counter(v.split(":")[0] for v in res.values())
    for name, v in sorted(res.items()):
        if v.split(":")[0] in ("SYNTH_FAIL", "GRAD_FAIL"):
            print(f"{name:40s} {v[:160]}")
    print(dict(counts), file=sys.stderr)
