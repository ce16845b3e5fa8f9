"""Pallas/ref parity for the sampler's fused elementwise kernels —
``ddim_fused``, ``parareal_update`` and the new fused-residual feed —
swept over f32/bf16, non-lane-multiple shapes (the padding path) and the
explicit ``interpret=True`` CPU entry points.

Unlike tests/test_kernels.py this file needs no ``hypothesis``: the parity
matrix here must run on every environment (it is the ground truth for
flipping the fused path on by default where kernels compile)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref, tuning

KEYS = jax.random.split(jax.random.PRNGKey(42), 4)

# tile sizes for tests come from the tuning seam (RL010): explicit tuner
# overrides, not raw integers at the dispatch call sites
TUNER32 = tuning.KernelTuner(overrides={"flash": {"block_q": 32,
                                                  "block_k": 32}})


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(7,), (128,), (33, 5), (4, 129), (1000,)])
def test_parareal_update_dtype_and_padding(shape, dtype):
    """Kernel/ref parity across dtypes and non-lane-multiple shapes (the
    padding path pads the flattened operands to a multiple of 128)."""
    dt = jnp.dtype(dtype)
    y = jax.random.normal(KEYS[0], shape, dt)
    c = jax.random.normal(KEYS[1], shape, dt)
    p = jax.random.normal(KEYS[2], shape, dt)
    out_k, r_k = ops.parareal_update(y, c, p, use_kernel=True)
    out_r, r_r = ref.parareal_update(y, c, p)
    assert out_k.shape == shape and out_k.dtype == dt
    tol = 2e-2 if dtype == "bfloat16" else 1e-6
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(float(r_k), float(r_r),
                               rtol=3e-2 if dtype == "bfloat16" else 1e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(13,), (256,), (33, 5), (4, 129)])
def test_parareal_update_residual_parity(shape, dtype):
    """The fused-residual kernel (per-tile L1 partials feeding the
    convergence norm) vs the jnp oracle, across dtypes + padding shapes."""
    dt = jnp.dtype(dtype)
    y, c, p, o = (jax.random.normal(k, shape, dt) for k in KEYS)
    out_k, r_k = ops.parareal_update_residual(y, c, p, o, use_kernel=True)
    out_r, r_r = ref.parareal_update_residual(y, c, p, o)
    assert out_k.shape == shape and out_k.dtype == dt
    tol = 2e-2 if dtype == "bfloat16" else 1e-6
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(float(r_k), float(r_r),
                               rtol=3e-2 if dtype == "bfloat16" else 1e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(3, 2, 7), (2, 3, 128), (4, 2, 33, 5),
                                   (2, 2, 129), (5, 4)])
def test_parareal_update_residual_per_block(shape, dtype):
    """The sliding-window frontier feed: ``batch_dims=2`` preserves the
    leading (block, sample) axes, emitting per-block per-sample L1
    partials — kernel vs oracle across dtypes and padding shapes (each
    (B, K) slice gets its own padded rows, so tiles never straddle)."""
    dt = jnp.dtype(dtype)
    y, c, p, o = (jax.random.normal(k, shape, dt) for k in KEYS)
    out_k, r_k = ops.parareal_update_residual(y, c, p, o, batch_dims=2,
                                              use_kernel=True)
    out_r, r_r = ref.parareal_update_residual(y, c, p, o, batch_dims=2)
    assert out_k.shape == shape and out_k.dtype == dt
    assert r_k.shape == r_r.shape == shape[:2]
    tol = 2e-2 if dtype == "bfloat16" else 1e-6
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(r_k, np.float32),
                               np.asarray(r_r, np.float32),
                               rtol=3e-2 if dtype == "bfloat16" else 1e-4)


def test_parareal_update_residual_batch_dims_contract():
    """batch_dims generalizes the legacy ``batched`` flag (0 == default,
    1 == batched=True) and rejects out-of-range reductions."""
    y, c, p, o = (jax.random.normal(k, (3, 5)) for k in KEYS)
    for use_kernel in (True, False):
        _, r0 = ops.parareal_update_residual(y, c, p, o, batch_dims=0,
                                             use_kernel=use_kernel)
        _, r0d = ops.parareal_update_residual(y, c, p, o,
                                              use_kernel=use_kernel)
        _, r1 = ops.parareal_update_residual(y, c, p, o, batch_dims=1,
                                             use_kernel=use_kernel)
        _, r1b = ops.parareal_update_residual(y, c, p, o, batched=True,
                                              use_kernel=use_kernel)
        assert r0.shape == r0d.shape == ()
        assert r1.shape == r1b.shape == (3,)
        np.testing.assert_allclose(np.asarray(r1), np.asarray(r1b))
        with pytest.raises(ValueError, match="batch_dims"):
            ops.parareal_update_residual(y, c, p, o, batch_dims=5,
                                         use_kernel=use_kernel)


@pytest.mark.parametrize("shape", [(3, 7), (2, 128), (4, 33, 5), (2, 129),
                                   (5, 1000)])
def test_parareal_update_residual_batched(shape):
    """Batched (K,) path: per-sample partials (rows are padded per sample
    so tiles never straddle samples) vs the oracle's per-sample sums."""
    y, c, p, o = (jax.random.normal(k, shape) for k in KEYS)
    out_k, r_k = ops.parareal_update_residual(y, c, p, o, batched=True,
                                              use_kernel=True)
    out_r, r_r = ref.parareal_update_residual(y, c, p, o, batched=True)
    assert r_k.shape == (shape[0],)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_r), rtol=1e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n", [1, 127, 128, 129, 4096])
def test_ddim_fused_padding_and_dtypes_interpret(n, dtype):
    """ddim_fused kernel/ref parity on CPU via interpret=True, pinned to
    the non-lane-multiple (padding) and exact-multiple row layouts."""
    from repro.kernels.elementwise import ddim_fused_pallas
    dt = jnp.dtype(dtype)
    x = jax.random.normal(KEYS[0], (n,), dt)
    e = jax.random.normal(KEYS[1], (n,), dt)
    a, b = 0.37, 0.61
    out = ops.ddim_fused(x, e, a, b, use_kernel=True)
    exp = ref.ddim_fused(x, e, a, b)
    assert out.shape == x.shape and out.dtype == dt
    tol = 1e-2 if dtype == "bfloat16" else 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)
    # and the raw 2D kernel entry point under explicit interpret=True
    rows = -(-n // 128)
    x2 = jnp.zeros((rows, 128), dt).at[0, 0].set(1.0)
    e2 = jnp.zeros((rows, 128), dt)
    ab = jnp.asarray([[a, b]], jnp.float32)
    o2 = ddim_fused_pallas(x2, e2, ab, interpret=True)
    exp2 = ref.ddim_fused(x2, e2, a, b)
    np.testing.assert_allclose(np.asarray(o2, np.float32),
                               np.asarray(exp2, np.float32),
                               rtol=tol, atol=tol)


def test_parareal_residual_kernel_interpret_entry_point():
    """The raw 2D fused-residual kernel under explicit interpret=True."""
    from repro.kernels.elementwise import parareal_update_residual_pallas
    y, c, p, o = (jax.random.normal(k, (6, 128)) for k in KEYS)
    # raw kernel entry point: the tile size IS the subject under test, so
    # the literal is intentional  # reprolint: disable=RL010
    out, partials = parareal_update_residual_pallas(y, c, p, o,
                                                    block_rows=2,
                                                    interpret=True)
    assert partials.shape == (3, 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(y + c - p),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        float(jnp.sum(partials)),
        float(jnp.sum(jnp.abs((y + c - p) - o))), rtol=1e-5)


# B, Hq, Hkv, Sq, Sk, D, causal — the DiT patch-sharding shapes: small
# bidirectional sequences (encoder-style), GQA, local-query-vs-full-KV
# (Sq < Sk, what the model-parallel K/V all-gather produces), and
# non-multiple-of-128 tiles
FLASH_CASES = [
    (2, 2, 2, 16, 16, 16, False),     # DiT-sized bidirectional block
    (1, 4, 2, 8, 32, 16, False),      # patch-sharded: local q, gathered kv
    (2, 4, 4, 64, 64, 32, True),      # causal, tile-exact
    (1, 8, 2, 48, 48, 24, True),      # GQA 4x + ragged tiles
    (1, 2, 2, 40, 104, 32, True),     # Sq < Sk, right-aligned causal mask
]


# The same fields, then where the sequence fits one tile at the default
# tiles (the batched forward, many (sample, head) rows a grid step):
# (leading vmap axis or None, block_b override or None, window)
BATCHED_FLASH_CASES = [
    (4, 12, 12, 64, 64, 64, False, None, None, None),  # served DiT eval
    (4, 12, 12, 64, 64, 64, False, 5, None, None),     # fine solves: 5 blocks
    (2, 4, 4, 40, 40, 32, True, None, None, None),     # causal, one tile
    (1, 4, 4, 48, 48, 16, True, None, None, 8),        # causal + window
    (2, 8, 2, 48, 48, 24, True, None, None, None),     # GQA 4x, one tile
    (1, 6, 6, 16, 16, 16, False, None, 4, None),       # block_b 4 ∤ bh 6
    (1, 8, 2, 16, 16, 16, True, None, 6, None),        # block_b 6, group 4
]


def _flash_case_id(c):
    cid = f"B{c[0]}H{c[1]}-{c[2]}S{c[3]}x{c[4]}D{c[5]}c{int(c[6])}"
    if len(c) == 7:
        return cid
    blocks, block_b, window = c[7:]
    return cid + "-onetile" + (f"-vmap{blocks}" if blocks else "") + (
        f"-bb{block_b}" if block_b else "") + (f"-w{window}" if window else "")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", FLASH_CASES + BATCHED_FLASH_CASES,
                         ids=_flash_case_id)
def test_flash_attention_interpret_parity(case, dtype):
    """Flash kernel (interpret mode on CPU) vs the jnp oracle — the parity
    matrix behind the sharded DiT denoiser's attention path, which feeds
    local queries and all-gathered K/V through ``ops.attention`` with
    ``use_kernel=True``; the one-tile cases take the batched forward
    (the served DiT shape, plain and vmapped over the fine solves'
    blocks).  Runs everywhere (no hypothesis dependency)."""
    b, hq, hkv, sq, sk, d, causal = case[:7]
    blocks, block_b, window = case[7:] or (None, None, None)
    if len(case) == 7:
        tuner = TUNER32
    elif block_b:
        tuner = tuning.KernelTuner(overrides={"flash": {"block_b": block_b}})
    else:
        tuner = tuning.KernelTuner(table_dir="/nonexistent")
    lead = (blocks, b) if blocks else (b,)
    dt = jnp.dtype(dtype)
    q = jax.random.normal(KEYS[0], (*lead, hq, sq, d), dt)
    k = jax.random.normal(KEYS[1], (*lead, hkv, sk, d), dt)
    v = jax.random.normal(KEYS[2], (*lead, hkv, sk, d), dt)

    def attn(q, k, v):
        return ops.attention(q, k, v, causal=causal, window=window,
                             tuner=tuner, use_kernel=True)

    def oracle(q, k, v):
        return ref.attention(q, k, v, causal=causal, window=window)

    if blocks:
        attn, oracle = jax.vmap(attn), jax.vmap(oracle)
    out = attn(q, k, v)
    exp = oracle(q, k, v)
    assert out.shape == exp.shape and out.dtype == dt
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


def test_fused_default_resolution():
    """fused_default is on exactly where compiled kernels exist (the
    TPU/GPU capability set) and never under FORCE_REF; the tri-state
    resolver honors explicit bools."""
    from repro.core.engine import resolve_fused
    # this test *is* the resolver's oracle, so the raw backend probe is
    # intentional here  # reprolint: disable=RL005
    compiled = jax.default_backend() in ops._COMPILED_BACKENDS
    assert ops.fused_default() == compiled
    assert resolve_fused(None) == compiled
    assert resolve_fused(True) is True
    assert resolve_fused(False) is False
    saved = ops.FORCE_REF
    try:
        ops.FORCE_REF = True
        assert ops.fused_default() is False
    finally:
        ops.FORCE_REF = saved


@pytest.fixture
def _fake_backend(monkeypatch):
    """Monkeypatch jax.default_backend (what ops probes), reset the
    one-shot warning latch, and pin FORCE_REF=False around each use —
    other test modules flip it True process-wide for CPU speed, which
    would mask the capability logic under test here."""
    def set_backend(name):
        monkeypatch.setattr(jax, "default_backend", lambda: name)
    monkeypatch.setattr(ops, "_warned_degraded", False)
    monkeypatch.setattr(ops, "FORCE_REF", False)
    yield set_backend


def test_fused_default_true_on_gpu(_fake_backend):
    """GPU is in the compiled capability tier: fused on, no warning."""
    _fake_backend("gpu")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert ops.fused_default() is True
        assert ops._interpret() is False
        assert ops._plat() == "gpu"


@pytest.mark.parametrize("backend", ["tpu", "gpu", "cpu"])
def test_fused_default_never_warns_on_known_tiers(_fake_backend, backend):
    """The degrade warning must never fire on tpu/gpu (compiled) or cpu
    (the known interpret-mode dev tier)."""
    _fake_backend(backend)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for _ in range(3):
            ops.fused_default()


def test_fused_default_warns_once_on_unsupported_backend(_fake_backend):
    """A backend with no Pallas lowering gets exactly one structured
    warning naming the knobs (including the tuning seam), then silence."""
    _fake_backend("rocm")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert ops.fused_default() is False
        assert ops.fused_default() is False      # second call: silent
    msgs = [w for w in caught if issubclass(w.category, UserWarning)]
    assert len(msgs) == 1
    text = str(msgs[0].message)
    assert "rocm" in text and "use_fused" in text and "FORCE_REF" in text
    assert "repro.kernels.tuning" in text


def test_fused_default_no_warning_under_force_ref(_fake_backend):
    """FORCE_REF pins the reference path deliberately — no warning even
    on an unsupported backend."""
    _fake_backend("rocm")
    saved = ops.FORCE_REF
    try:
        ops.FORCE_REF = True
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert ops.fused_default() is False
    finally:
        ops.FORCE_REF = saved


# ---------------------------------------------------------------------------
# Tuned-config parity matrix: (default | table-resolved | override) configs
# x f32/bf16 x non-tile-multiple shapes, interpret mode (ISSUE 10)
# ---------------------------------------------------------------------------

def _table_for(kernel, dtype, shape, params):
    """An in-memory one-entry tuning table hitting exactly this lookup."""
    return tuning.KernelTuner(tables={"cpu": {
        "version": tuning.TABLE_SCHEMA_VERSION, "backend": "cpu",
        "entries": [{"kernel": kernel, "dtype": jnp.dtype(dtype).name,
                     "bucket": list(tuning.bucket_for(kernel, shape)),
                     "params": params}]}})


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cfgname", ["default", "table", "override"])
def test_elementwise_tuned_config_parity(dtype, cfgname):
    """parareal_update_residual under all three tuner resolution tiers:
    f32 main outputs are *bitwise* vs ref (same op order per element);
    the reduction partials differ only in summation order (tolerance)."""
    dt = jnp.dtype(dtype)
    shape = (3, 129)                 # non-lane-multiple -> padding path
    y, c, p, o = (jax.random.normal(k, shape, dt) for k in KEYS)
    if cfgname == "default":
        tuner = tuning.KernelTuner(table_dir="/nonexistent")
        want_src = "heuristic"
    elif cfgname == "table":
        tuner = _table_for("elementwise", dt, shape, {"tile_rows": 2})
        want_src = "table"
    else:
        tuner = tuning.KernelTuner(
            overrides={"elementwise": {"tile_rows": 1}})
        want_src = "override"
    assert tuner.resolve("elementwise", backend="cpu", dtype=dt,
                         shape=shape).source == want_src
    out_k, r_k = ops.parareal_update_residual(y, c, p, o, tuner=tuner,
                                              use_kernel=True)
    out_r, r_r = ref.parareal_update_residual(y, c, p, o)
    if dtype == "float32":
        assert np.array_equal(np.asarray(out_k), np.asarray(out_r))
    else:
        np.testing.assert_allclose(np.asarray(out_k, np.float32),
                                   np.asarray(out_r, np.float32),
                                   rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(float(r_k), float(r_r),
                               rtol=3e-2 if dtype == "bfloat16" else 1e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("cfgname", ["default", "table", "override",
                                     "block_b"])
def test_flash_tuned_config_parity(dtype, cfgname):
    """ops.attention under all three tuner resolution tiers on a
    non-tile-multiple GQA case (boundary buckets); ``block_b`` overrides
    the batched forward's rows a step with a cap that divides neither
    B*Hq=4 nor lands on the GQA group of 2.  The default tier batches
    (sample, head) rows only where the sequence fits one tile."""
    b, hq, hkv, sq, sk, d, causal = 1, 4, 2, 33, 49, 16, True
    dt = jnp.dtype(dtype)
    q = jax.random.normal(KEYS[0], (b, hq, sq, d), dt)
    k = jax.random.normal(KEYS[1], (b, hkv, sk, d), dt)
    v = jax.random.normal(KEYS[2], (b, hkv, sk, d), dt)
    if cfgname == "default":
        tuner = tuning.KernelTuner(table_dir="/nonexistent")
        want_src = "heuristic"
    elif cfgname == "table":
        tuner = _table_for("flash", dt, (sq, sk, d),
                           {"block_q": 16, "block_k": 8})
        want_src = "table"
    elif cfgname == "override":
        tuner = TUNER32
        want_src = "override"
    else:
        tuner = tuning.KernelTuner(overrides={"flash": {"block_b": 3}})
        want_src = "override"
        assert tuning.pick_block_b(b * hq, hq // hkv, 3) == 2
    cfg = tuner.resolve("flash", backend="cpu", dtype=dt, shape=(sq, sk, d))
    assert cfg.source == want_src
    if cfgname == "default":
        assert cfg.params["block_b"] > 1
        committed = tuning.KernelTuner()
        for backend in ("tpu", "cpu"):
            def block_b(shape):
                return committed.resolve("flash", backend=backend, dtype=dt,
                                         shape=shape).params["block_b"]
            assert block_b((64, 64, 64)) > 1
            assert block_b((2048, 2048, 128)) == 1
    out = ops.attention(q, k, v, causal=causal, tuner=tuner,
                        use_kernel=True)
    exp = ref.attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# GPU (Triton-structured) kernel family, exercised via interpret=True
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "case", FLASH_CASES,
    ids=lambda c: f"B{c[0]}H{c[1]}-{c[2]}S{c[3]}x{c[4]}D{c[5]}c{int(c[6])}")
def test_flash_gpu_family_interpret_parity(case, dtype):
    """The Triton-structured flash kernels (in-kernel KV loop, register
    carries) against the same oracle matrix as the TPU family — pinned on
    CPU via interpret=True, plat="gpu"."""
    b, hq, hkv, sq, sk, d, causal = case
    dt = jnp.dtype(dtype)
    q = jax.random.normal(KEYS[0], (b, hq, sq, d), dt)
    k = jax.random.normal(KEYS[1], (b, hkv, sk, d), dt)
    v = jax.random.normal(KEYS[2], (b, hkv, sk, d), dt)
    out = ops.attention(q, k, v, causal=causal, tuner=TUNER32, plat="gpu",
                        use_kernel=True)
    exp = ref.attention(q, k, v, causal=causal)
    assert out.shape == exp.shape and out.dtype == dt
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [None, 7])
def test_flash_gpu_family_grads_and_window(window):
    """Backward parity for the GPU family (dq/dkv kernels with in-kernel
    loops), including the sliding-window live-tile loop bounds."""
    b, hq, hkv, sq, sk, d = 1, 4, 2, 33, 33, 8
    q = jax.random.normal(KEYS[0], (b, hq, sq, d))
    k = jax.random.normal(KEYS[1], (b, hkv, sk, d))
    v = jax.random.normal(KEYS[2], (b, hkv, sk, d))

    def loss(fn):
        return jax.grad(lambda q, k, v: jnp.sum(jnp.cos(fn(q, k, v))),
                        argnums=(0, 1, 2))(q, k, v)

    g_ref = loss(lambda q, k, v: ref.attention(q, k, v, causal=True,
                                               window=window))
    g_gpu = loss(lambda q, k, v: ops.attention(
        q, k, v, causal=True, window=window, tuner=TUNER32, plat="gpu",
        use_kernel=True))
    for a, bb in zip(g_ref, g_gpu):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-4, atol=2e-5)


def test_rwkv6_gpu_family_interpret_parity():
    """The streaming GPU WKV kernel (single fori_loop, register-resident
    state) vs the oracle — including a T that the TPU chunking would
    split, which the GPU family ignores."""
    bsz, h, t, dk, dv = 2, 2, 24, 8, 12
    ks = jax.random.split(KEYS[3], 5)
    r = jax.random.normal(ks[0], (bsz, h, t, dk))
    k = jax.random.normal(ks[1], (bsz, h, t, dk))
    v = jax.random.normal(ks[2], (bsz, h, t, dv))
    w = jax.random.normal(ks[3], (bsz, h, t, dk))
    u = jax.random.normal(ks[4], (h, dk))
    out_k, s_k = ops.rwkv6_wkv(r, k, v, w, u, plat="gpu", use_kernel=True)
    out_r, s_r = ref.rwkv6_wkv(r, k, v, w, u,
                               jnp.zeros((bsz, h, dk, dv), jnp.float32))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r),
                               rtol=1e-5, atol=1e-5)
