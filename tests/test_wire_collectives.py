"""Quantized collectives beyond gradients (round 13): block-scaled
int8/fp8 all-gather and ppermute on the weight / activation wire.

Covers the compression.py primitives (wire math, exact-self patch,
error-feedback round-trip stability), the FusedTrainStep threading
(zero=1/2/3 weight gathers, pipeline activation ppermute + last-stage
broadcast, widened {"grads","weights","activations"} config with its
degrade matrix), the eager MultiTensorUpdater gathers (stage<=2
post-update gather, stage-3 lazy materialize + compressed lookahead
prefetch), the kvstore gathered-byte accounting fix, and the
zero-extra-compile + telemetry riders. Loss parity bars are RELATIVE:
int8 block scaling carries ~0.4% max element error, fp8-e4m3 ~3% (3
mantissa bits), and SGD momentum amplifies nothing on these depths."""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt_mod
from mxnet_tpu import telemetry as _tm
from mxnet_tpu import tracing
from jax import shard_map
from mxnet_tpu.gluon.loss import L2Loss
from mxnet_tpu.gluon.parameter import Parameter
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.compression import (
    DEFAULT_BLOCK, block_dequantize, block_quantize,
    quantized_all_gather, quantized_all_gather_ef, wire_nbytes)
from mxnet_tpu.parallel.data_parallel import FusedTrainStep
from mxnet_tpu.parallel.mesh import hybrid_mesh

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


# -- primitives --------------------------------------------------------------

def test_wire_nbytes_math():
    # block=128: nb codes-bytes per block + 4 scale-bytes per block
    assert wire_nbytes(1024, "int8", 128) == 8 * 128 + 8 * 4
    assert wire_nbytes(1024, "fp8", 128) == 8 * 128 + 8 * 4
    assert wire_nbytes(1000, "int8", 128) == 8 * 128 + 8 * 4  # pads up
    assert wire_nbytes(1024, None, 128) == 4096  # uncompressed fp32
    # the headline cut at block 128
    assert 4096 / wire_nbytes(1024, "int8", 128) == pytest.approx(
        3.879, abs=1e-3)


@pytest.mark.parametrize("scheme,tol", [("int8", 0.006), ("fp8", 0.07)])
def test_block_quantize_roundtrip(scheme, tol):
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(1000).astype(np.float32) * 5.0)
    codes, scales = block_quantize(x, scheme, DEFAULT_BLOCK)
    assert codes.shape == (8, 128) and scales.shape == (8, 1)
    assert codes.dtype == (jnp.int8 if scheme == "int8"
                           else jnp.float8_e4m3fn)
    out = block_dequantize(codes, scales, n=1000)
    err = float(jnp.max(jnp.abs(out - x)))
    assert err < tol * float(jnp.max(jnp.abs(x))), err
    # fp8 out-of-range cast would be nan without the pre-cast clip
    assert bool(jnp.all(jnp.isfinite(out)))


def _dp_mesh():
    return make_mesh([len(jax.devices())], ["dp"])


def test_quantized_all_gather_exact_self():
    """The owner's own slice of the gathered result is bit-exact (the
    drift-free master chain relies on it); other slices carry bounded
    quantization error."""
    mesh = _dp_mesh()
    n = len(jax.devices())
    P = jax.sharding.PartitionSpec
    rs = np.random.RandomState(1)
    full = jnp.asarray(rs.randn(n * 256).astype(np.float32))

    def body(v):
        return quantized_all_gather(v, "dp", "int8", DEFAULT_BLOCK)

    out = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp"),
                            out_specs=P("dp"), check_vma=False))(
        jax.device_put(full, jax.sharding.NamedSharding(mesh, P("dp"))))
    # out is (n*n*256,) stacked per-device gathers; device i's copy of
    # slice i must be bitwise the original
    got = np.asarray(out).reshape(n, n * 256)
    ref = np.asarray(full).reshape(n, 256)
    for i in range(n):
        own = got[i, i * 256:(i + 1) * 256]
        np.testing.assert_array_equal(own, ref[i])
        other = got[i, (i + 1) % n * 256:((i + 1) % n + 1) * 256]
        err = np.max(np.abs(other - ref[(i + 1) % n]))
        assert 0 < err < 0.05, err


def test_error_feedback_round_trip_stable():
    """ZeRO-3 residual mode: 3 repeated gathers of the SAME shard keep
    the owner slice bit-exact every round, and the error-feedback
    residual makes the time-average of the dequantized estimate beat
    any single-shot estimate (EF's convergence-on-constants)."""
    mesh = _dp_mesh()
    n = len(jax.devices())
    P = jax.sharding.PartitionSpec
    rs = np.random.RandomState(2)
    full = jnp.asarray(rs.randn(n * 256).astype(np.float32))
    shard_spec = jax.sharding.NamedSharding(mesh, P("dp"))
    x = jax.device_put(full, shard_spec)
    res = jax.device_put(jnp.zeros_like(full), shard_spec)

    def body(v, r):
        return quantized_all_gather_ef(v, r, "dp", "int8",
                                       DEFAULT_BLOCK)

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("dp"), P("dp")),
                          out_specs=(P("dp"), P("dp")),
                          check_vma=False))
    ref = np.asarray(full).reshape(n, 256)
    outs = []
    for _ in range(3):
        out, res = f(x, res)
        got = np.asarray(out).reshape(n, n * 256)
        for i in range(n):  # owner slice: bitwise every round
            np.testing.assert_array_equal(
                got[i, i * 256:(i + 1) * 256], ref[i])
        outs.append(got)
    one_shot = np.max(np.abs(outs[0][0, 256:512] - ref[1]))
    averaged = np.max(np.abs(np.mean([o[0, 256:512] for o in outs],
                                     axis=0) - ref[1]))
    assert averaged <= one_shot * 1.5 + 1e-6, (averaged, one_shot)


# -- fused parity matrix -----------------------------------------------------

def _toy():
    mx.random.seed(0)
    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(16, activation="relu"),
            mx.gluon.nn.Dense(3))
    net.initialize()
    return net


def _run_zero(zero, comp, steps=3):
    net = _toy()
    mesh = _dp_mesh()
    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
    step = FusedTrainStep(net, L2Loss(), opt, mesh=mesh, zero=zero,
                          compression=comp)
    rs = np.random.RandomState(42)
    losses = []
    for _ in range(steps):
        x = NDArray(jnp.asarray(rs.rand(32, 8), jnp.float32))
        y = NDArray(jnp.asarray(rs.rand(32, 3), jnp.float32))
        losses.append(float(step(x, y)))
    return losses, step


def _rel(a, b):
    return max(abs(x - y) / max(abs(y), 1e-6) for x, y in zip(a, b))


@pytest.mark.parametrize("zero", [1, 2, 3])
@pytest.mark.parametrize("scheme", ["int8", "fp8"])
def test_fused_weight_gather_parity(zero, scheme):
    l_ref, s_ref = _run_zero(zero, None)
    l_q, s_q = _run_zero(zero, {"weights": scheme})
    rel = _rel(l_q, l_ref)
    assert rel < (0.08 if scheme == "fp8" else 0.03), rel
    lg, wr = s_q._wire_gathered
    assert lg / wr >= 3.5, (lg, wr)
    assert s_ref._wire_gathered[0] == s_ref._wire_gathered[1]


def test_fused_zero3_residual_parity():
    l_ref, _ = _run_zero(3, None)
    l_res, s = _run_zero(3, {"weights": {"type": "int8",
                                         "residual": True}})
    assert _rel(l_res, l_ref) < 0.03
    assert s._wire_gathered[0] / s._wire_gathered[1] >= 3.5


def test_fused_grads_plus_weights():
    """The widened config composes: the grads leg behaves exactly like
    the legacy flat dict while weights ride the new wire."""
    l_gw, s_gw = _run_zero(2, {"grads": "int8", "weights": "int8"})
    l_g, _ = _run_zero(2, {"type": "int8"})
    assert _rel(l_gw, l_g) < 0.05
    assert s_gw.compression is not None
    assert s_gw._wire_weights is not None


def test_fused_zero_extra_compiles():
    """Quantized wire adds ZERO executables: scales are traced, so
    repeated same-shape steps never retrace."""
    _, step = _run_zero(3, {"weights": "int8"})
    tracing.reset_cache_stats()
    rs = np.random.RandomState(3)
    for _ in range(2):
        x = NDArray(jnp.asarray(rs.rand(32, 8), jnp.float32))
        y = NDArray(jnp.asarray(rs.rand(32, 3), jnp.float32))
        float(step(x, y))
    st = tracing.cache_stats()["per_block"]
    assert all(v["compiles"] == 0 for v in st.values()), st


# -- pipeline activation wire ------------------------------------------------

def _dense_chain(n, seed=1, width=128):
    mx.random.seed(seed)
    net = mx.gluon.nn.HybridSequential()
    for _ in range(n):
        net.add(mx.gluon.nn.Dense(width))
    net.initialize()
    return net


def _run_pipe(comp, steps=2):
    net = _dense_chain(8)
    mesh = hybrid_mesh(dp=2, pp=4)
    opt = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9)
    step = FusedTrainStep(net, L2Loss(), opt, mesh=mesh, pipeline=8,
                          zero=1, compression=comp)
    rs = np.random.RandomState(42)
    losses = []
    for _ in range(steps):
        x = NDArray(jnp.asarray(rs.rand(32, 128), jnp.float32))
        y = NDArray(jnp.asarray(rs.rand(32, 128), jnp.float32))
        losses.append(float(step(x, y)))
    return losses, step


def test_pipeline_activation_wire_parity():
    lp_ref, sp_ref = _run_pipe(None)
    lp_q, sp_q = _run_pipe({"weights": "int8", "activations": "fp8"})
    lp_a8, _ = _run_pipe({"activations": "int8"})
    assert _rel(lp_q, lp_ref) < 0.10
    assert _rel(lp_a8, lp_ref) < 0.05
    plg, pwr = sp_q._wire_permuted
    assert plg / pwr >= 3.5, (plg, pwr)
    glg, gwr = sp_q._wire_gathered
    assert glg / gwr >= 3.5, (glg, gwr)
    assert sp_ref._wire_permuted[0] == sp_ref._wire_permuted[1]


def test_trainer_pipeline_forwards_activation_compression():
    """Trainer(pipeline=M) used to drop compression={"activations":...}
    before the pipeline builder ever saw it (the no-pipeline degrade
    fired on the forwarded config). The request now rides through the
    Trainer into the fused step: no degrade warning, wire accounting
    shows the int8 cut on BOTH requested axes, and the lowered HLO moves
    8-bit payloads on each one (collective_permute for the activation
    hops, all_gather for the ZeRO weight gathers)."""
    net = _dense_chain(8)
    mesh = hybrid_mesh(dp=2, pp=4)
    net.initialize()
    tr = mx.gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, kvstore="device",
        compression_params={"activations": "int8", "weights": "int8"},
        zero=1, pipeline=8)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        step = FusedTrainStep(net, L2Loss(), tr, mesh=mesh)
    assert not any("activation" in str(x.message) for x in w), \
        [str(x.message) for x in w]
    rs = np.random.RandomState(42)
    x = NDArray(jnp.asarray(rs.rand(32, 128), jnp.float32))
    y = NDArray(jnp.asarray(rs.rand(32, 128), jnp.float32))
    float(step(x, y))
    plg, pwr = step._wire_permuted
    assert plg / pwr >= 3.5, (plg, pwr)
    glg, gwr = step._wire_gathered
    assert glg / gwr >= 3.5, (glg, gwr)
    hyper = {"lr": jnp.asarray(0.1, jnp.float32),
             "wd": jnp.asarray(0.0, jnp.float32),
             "t": jnp.asarray(1, jnp.int32),
             "rescale": jnp.asarray(1.0, jnp.float32)}
    key = jax.random.PRNGKey(0)
    txt = step._compiled.lower(step._tr, step._pp_mask, step._states,
                               hyper, key, x._data, y._data).as_text()
    lines = txt.splitlines()
    assert any("collective-permute" in ln and ("u8" in ln or "s8" in ln)
               for ln in lines) or \
        any("collective_permute" in ln and "i8" in ln for ln in lines), \
        "no 8-bit activation hop in the lowered step"
    assert any(("all-gather" in ln or "all_gather" in ln)
               and ("u8" in ln or "s8" in ln or "i8" in ln)
               for ln in lines), \
        "no 8-bit weight gather in the lowered step"


def test_wire_dtypes_in_lowered_collectives():
    """The lowered StableHLO moves 1-byte payloads: collective_permute
    carries f8E4M3FN, all_gather carries i8 — proof the compression is
    INSIDE the collective, not wrapped around a fp32 one."""
    from mxnet_tpu.parallel.compression import quantized_ppermute
    mesh = _dp_mesh()
    n = len(jax.devices())
    P = jax.sharding.PartitionSpec
    perm = tuple((i, (i + 1) % n) for i in range(n))
    x = jnp.zeros((n * 128,), jnp.float32)
    f = jax.jit(shard_map(
        lambda v: quantized_ppermute(v, "dp", perm, "fp8",
                                     DEFAULT_BLOCK),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        check_vma=False))
    txt = f.lower(x).as_text()
    assert any("collective_permute" in ln and "f8E4M3FN" in ln
               for ln in txt.splitlines()), txt[:2000]
    g = jax.jit(shard_map(
        lambda v: quantized_all_gather(v, "dp", "int8", DEFAULT_BLOCK),
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"),
        check_vma=False))
    txt = g.lower(x).as_text()
    assert any("all_gather" in ln and "xi8>" in ln
               for ln in txt.splitlines()), txt[:2000]


# -- degrade matrix ----------------------------------------------------------

def test_degrade_warns_and_rejects():
    mesh = _dp_mesh()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        FusedTrainStep(_toy(), L2Loss(), opt_mod.create("sgd"),
                       mesh=mesh, compression={"weights": "int8"})
        msgs = [str(x.message) for x in w]
    assert any("weight" in m and "zero" in m.lower() for m in msgs), msgs
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        FusedTrainStep(_toy(), L2Loss(), opt_mod.create("sgd"),
                       mesh=mesh, zero=2,
                       compression={"weights": {"type": "int8",
                                                "residual": True}})
        msgs = [str(x.message) for x in w]
    assert any("residual" in m for m in msgs), msgs
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        FusedTrainStep(_toy(), L2Loss(), opt_mod.create("sgd"),
                       mesh=mesh, zero=1,
                       compression={"activations": "int8"})
        msgs = [str(x.message) for x in w]
    assert any("activation" in m for m in msgs), msgs
    with pytest.raises(ValueError, match="wire compression supports"):
        FusedTrainStep(_toy(), L2Loss(), opt_mod.create("sgd"),
                       mesh=mesh, zero=1,
                       compression={"weights": "2bit"})


# -- eager updater wire ------------------------------------------------------

EAGER_SHAPES = [(256,), (128, 4), (640,), (2, 2, 2), (7,)]


def _make_trainer(zero, compression=None, seed=0):
    rs = np.random.RandomState(seed)
    params = {}
    for i, s in enumerate(EAGER_SHAPES):
        p = Parameter(f"p{i}", shape=s, dtype="float32")
        p.initialize()
        p.set_data(rs.randn(*s).astype(np.float32))
        params[f"p{i}"] = p
    tr = mx.gluon.Trainer(
        params, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
        kvstore="device", compression_params=compression, zero=zero)
    return params, tr


def _set_grads(params, seed):
    rs = np.random.RandomState(seed)
    for p in params.values():
        p.data()._grad._data = jnp.asarray(
            rs.randn(*p.shape)).astype(jnp.float32)


def _run_eager(zero, comp, steps=4):
    params, tr = _make_trainer(zero, comp)
    for step in range(steps):
        _set_grads(params, step)
        tr.step(batch_size=2)
    return {k: p.data().asnumpy() for k, p in params.items()}, tr


@pytest.mark.parametrize("zero", [2, 3])
@pytest.mark.parametrize("scheme,tol", [("int8", 0.05), ("fp8", 0.35)])
def test_eager_weight_gather_parity(zero, scheme, tol):
    ref, _ = _run_eager(zero, None)
    q, tr = _run_eager(zero, {"weights": scheme})
    dev = max(float(np.max(np.abs(q[k] - ref[k]))) for k in ref)
    # lossy materialized replicas, but the authoritative sharded chain
    # is exact: deviation is bounded by ONE quantization, not steps
    assert 0 < dev < tol, (zero, scheme, dev)
    assert tr._mt_updater._wcomp is not None


def test_eager_no_drift_accumulation():
    ref2, _ = _run_eager(3, None, steps=2)
    q2, _ = _run_eager(3, {"weights": "int8"}, steps=2)
    ref10, _ = _run_eager(3, None, steps=10)
    q10, _ = _run_eager(3, {"weights": "int8"}, steps=10)
    d2 = max(float(np.max(np.abs(q2[k] - ref2[k]))) for k in ref2)
    d10 = max(float(np.max(np.abs(q10[k] - ref10[k]))) for k in ref10)
    assert d10 < 4 * max(d2, 1e-3), (d2, d10)


def test_eager_zero3_compressed_prefetch():
    """Stage-3 lazy materialize dispatches (codes, scales) futures; the
    lookahead prefetch holds the compressed pair, not the fp32 bucket."""
    params, tr = _make_trainer(3, {"weights": "int8"})
    _set_grads(params, 0)
    tr.step(batch_size=2)
    # shrink to multi-bucket by rebuilding the updater with tiny buckets
    from mxnet_tpu.multi_tensor import MultiTensorUpdater
    up = MultiTensorUpdater(tr._optimizer, bucket_bytes=1024, stage=3,
                            weight_compression="int8")
    tr._mt_updater = up
    _set_grads(params, 1)
    tr.step(batch_size=2)
    zg = next(iter(up._zgroups.values()))
    assert len(zg.plans) > 1
    assert not isinstance(params["p0"]._data._data, jax.Array)
    _ = params["p0"].data()  # materialize bucket 0 + prefetch bucket 1
    assert zg.inflight, "lookahead prefetch missing"
    fut = next(iter(zg.inflight.values()))
    assert isinstance(fut, (tuple, list)) and len(fut) == 2
    assert fut[0].dtype == jnp.int8
    rb = up.zero_resident_bytes()
    assert rb["transient"] > 0


def test_eager_degrade_warns():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        _run_eager(2, {"weights": {"type": "int8", "residual": True}},
                   steps=1)
        msgs = [str(x.message) for x in w]
    assert any("residual" in m for m in msgs), msgs
    from mxnet_tpu.multi_tensor import MultiTensorUpdater
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        MultiTensorUpdater(opt_mod.create("sgd"), stage=0,
                           weight_compression="int8")
        msgs = [str(x.message) for x in w]
    assert any("ZeRO" in m for m in msgs), msgs


# -- telemetry byte accounting ----------------------------------------------

def test_fused_gathered_counters():
    _tm.enable()
    try:
        _run_zero(3, {"weights": "int8"}, steps=2)
        text = _tm.to_prometheus()
        lines = [ln for ln in text.splitlines()
                 if "comm_bytes_gathered" in ln and "fused" in ln]
        assert any("kind=logical" in ln for ln in lines), text
        assert any("kind=wire" in ln for ln in lines), text
    finally:
        _tm.disable()


def test_eager_gathered_counters_cut():
    _tm.enable()
    try:
        _run_eager(3, {"weights": "int8"}, steps=2)
        text = _tm.to_prometheus()
        lines = [ln for ln in text.splitlines()
                 if "comm_bytes_gathered" in ln and "zero3" in ln]
        vals = {}
        for ln in lines:
            key = "logical" if "kind=logical" in ln else "wire"
            vals[key] = vals.get(key, 0.0) + float(ln.rsplit(" ", 1)[1])
        assert vals["logical"] / vals["wire"] >= 3.5, vals
    finally:
        _tm.disable()


def test_flight_records_wire_collectives():
    """The flight ring sees every new wire site: the fused in-step
    gather, the eager stage<=2 post-update gather, and the stage-3
    just-in-time gather — entry carries the wire bytes, done the
    duration (a hang shows as entry-without-done)."""
    from mxnet_tpu import flight as _fl
    _fl.enable()
    try:
        _fl.clear()
        _run_zero(3, {"weights": "int8"}, steps=1)
        sites = [s for (_, k, s, _) in _fl.events()
                 if k == "collective"]
        assert "fused.all_gather" in sites, sites
        _fl.clear()
        _run_eager(2, {"weights": "int8"}, steps=1)
        sites = [s for (_, k, s, _) in _fl.events()
                 if k == "collective"]
        assert "zero.weight_gather" in sites, sites
        _fl.clear()
        _run_eager(3, {"weights": "int8"}, steps=1)
        evs = _fl.events()
        entry = [(s, p) for (_, k, s, p) in evs if k == "collective"]
        done = [s for (_, k, s, _) in evs if k == "collective_done"]
        assert any(s == "zero3.gather" for (s, _) in entry), entry
        assert "zero3.gather" in done
        pay = next(p for (s, p) in entry if s == "zero3.gather")
        assert pay.get("bytes", 0) > 0, pay
    finally:
        _fl.disable()
        _fl.clear()


def test_kvstore_widened_compression_and_gathered_wire():
    """Satellite fix: gathered-direction bytes count the WIRE size when
    weight compression is set (the old code only ever compressed the
    pushed/reduced direction)."""
    from mxnet_tpu.kvstore import create as kv_create
    kv = kv_create("local")
    kv.set_gradient_compression({"grads": {"type": "2bit"},
                                 "weights": "int8"})
    assert kv._compression["type"] == "2bit"
    assert kv._weight_compression["type"] == "int8"
    _tm.enable()
    try:
        v = NDArray(jnp.zeros((1024,), jnp.float32))
        kv.init(0, v)
        kv.pull(0, out=NDArray(jnp.zeros((1024,), jnp.float32)))
        text = _tm.to_prometheus()
        lines = [ln for ln in text.splitlines()
                 if "comm_bytes_gathered" in ln and "local" in ln]
        vals = {("logical" if "kind=logical" in ln else "wire"):
                float(ln.rsplit(" ", 1)[1]) for ln in lines}
        assert vals["logical"] == 4096, vals
        assert vals["wire"] == 1024 + 8 * 4, vals
    finally:
        _tm.disable()
    with pytest.raises(ValueError, match="wire compression supports"):
        kv.set_gradient_compression({"weights": "2bit"})
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        kv.set_gradient_compression({"activations": "int8"})
        msgs = [str(x.message) for x in w]
    assert any("activation" in m for m in msgs), msgs
    assert kv._compression is None
