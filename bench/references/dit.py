"""Plain reference for the DiT family: seeded weights, a float32 forward
pass and the sequential DDIM solve, in straightforward ``jax.numpy``.

It imports nothing of the program under test.  The weights are made here,
from the seed, in the parameter layout that the program's DiT reads
(``patch_in``, ``pos``, ``t_mlp1``, ``t_mlp2``, stacked ``blocks``,
``ln_f``, ``mod_f``, ``mod_fb``, ``patch_out``), so both sides run on the
same numbers.  Every leaf is drawn live: a DiT's adaLN-zero start
(modulation and output projection at zero) would return exactly zero.

The forward follows the published DiT block (Peebles & Xie, 2022) with the
program's two departures, which the configuration file lists:

* RMSNorm (eps 1e-6, no mean subtraction) where DiT uses LayerNorm; the
  block norms carry no parameters, the final norm a learned scale;
* no bias on the q/k/v/o projections;

and the program's order of the six adaLN chunks, which differs from
DiT's (shift, scale, gate of attention, then of the MLP): here they are
shift and scale of the attention input, shift of the MLP input, gate of
attention, scale of the MLP input, gate of the MLP.  With seeded weights
the order changes no distribution, only which weights meet which role.

``precision`` picks how matmul operands are rounded: ``"float32"`` is the
reference (every product at ``Precision.HIGHEST``); ``"float8"`` is the
control, with each matmul operand scaled by its absolute maximum and
rounded to float8_e4m3fn, one step below the bfloat16 that the
configuration serves in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
# the control's precision: the step below the bfloat16 the program serves in
CONTROL = "float8"
F8_MAX = float(jnp.finfo(F8).max)


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def tokens(cfg) -> int:
    return (cfg["image_size"] // cfg["patch_size"]) ** 2


def sample_shape(cfg):
    return (cfg["image_size"], cfg["image_size"], cfg["in_channels"])


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------

def _draw(cfg, key):
    d, f, L = cfg["d_model"], cfg["d_ff"], cfg["num_layers"]
    p_in = cfg["patch_size"] ** 2 * cfg["in_channels"]
    live = cfg["live_std"]
    dt = _dtype(cfg["dtype"])
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dt)

    blocks = {
        "attn": {"wq": normal((L, d, d), d ** -0.5),
                 "wk": normal((L, d, d), d ** -0.5),
                 "wv": normal((L, d, d), d ** -0.5),
                 "wo": normal((L, d, d), d ** -0.5)},
        "mlp": {"w_up": normal((L, d, f), d ** -0.5),
                "w_down": normal((L, f, d), f ** -0.5)},
        "mod": normal((L, d, 6 * d), live),
        "mod_b": normal((L, 6 * d), live),
    }
    return {
        "patch_in": normal((p_in, d), p_in ** -0.5),
        "pos": normal((tokens(cfg), d), 0.02),
        "t_mlp1": normal((256, d), 256 ** -0.5),
        "t_mlp2": normal((d, d), d ** -0.5),
        "blocks": blocks,
        "ln_f": {"scale": jnp.ones((d,), jnp.float32)},
        "mod_f": normal((d, 2 * d), live),
        "mod_fb": normal((2 * d,), live),
        "patch_out": normal((d, p_in), live),
    }


def make_params(cfg, seed: int):
    """The weights for ``seed``, drawn on the device in one jitted call,
    in the type they are served in."""
    key = jax.random.PRNGKey(seed)
    return jax.jit(functools.partial(_draw, cfg))(key)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _quantize(a):
    """Round to float8_e4m3fn under a per-tensor absmax scale, back in f32."""
    a = a.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(F8).astype(jnp.float32) * s


def _mm(spec, a, b, precision):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "float8":
        a, b = _quantize(a), _quantize(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale=None, eps=1e-6):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x if scale is None else x * scale


def _time_embed(t, dim=256, max_period=10_000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


def _block(cfg, p, x, c, precision):
    """One DiT block; ``c`` is silu of the time embedding, (b, d)."""
    b, n, d = x.shape
    h = cfg["num_heads"]
    hd = d // h
    mod = _mm("bd,de->be", c, p["mod"], precision) + p["mod_b"].astype(
        jnp.float32)
    shift_a, scale_a, shift_m, gate_a, scale_m, gate_m = jnp.split(mod, 6, -1)

    a_in = _rms(x) * (1 + scale_a[:, None]) + shift_a[:, None]
    q = _mm("bnd,de->bne", a_in, p["attn"]["wq"], precision)
    k = _mm("bnd,de->bne", a_in, p["attn"]["wk"], precision)
    v = _mm("bnd,de->bne", a_in, p["attn"]["wv"], precision)
    q, k, v = (t.reshape(b, n, h, hd) for t in (q, k, v))
    s = _mm("bqhd,bkhd->bhqk", q, k, precision) * hd ** -0.5
    o = _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision)
    attn = _mm("bnd,de->bne", o.reshape(b, n, d), p["attn"]["wo"], precision)
    x = x + gate_a[:, None] * attn

    m_in = _rms(x) * (1 + scale_m[:, None]) + shift_m[:, None]
    up = _mm("bnd,df->bnf", m_in, p["mlp"]["w_up"], precision)
    mlp = _mm("bnf,fd->bnd", jax.nn.gelu(up, approximate=True),
              p["mlp"]["w_down"], precision)
    return x + gate_m[:, None] * mlp


def forward(cfg, params, x_img, t, precision="float32"):
    """eps prediction for ``x_img`` (b, H, W, C) at times ``t`` (b,)."""
    b, hh, ww, ch = x_img.shape
    p = cfg["patch_size"]
    gh, gw = hh // p, ww // p
    patches = x_img.reshape(b, gh, p, gw, p, ch).transpose(0, 1, 3, 2, 4, 5)
    patches = patches.reshape(b, gh * gw, p * p * ch)
    x = _mm("bnp,pd->bnd", patches, params["patch_in"], precision) \
        + params["pos"][:gh * gw].astype(jnp.float32)[None]
    temb = jax.nn.silu(_mm("bk,kd->bd", _time_embed(t), params["t_mlp1"],
                           precision))
    temb = _mm("bd,de->be", temb, params["t_mlp2"], precision)
    c = jax.nn.silu(temb)

    def body(x, pb):
        return _block(cfg, pb, x, c, precision), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    mod = _mm("bd,de->be", c, params["mod_f"], precision) \
        + params["mod_fb"].astype(jnp.float32)
    shift_f, scale_f = jnp.split(mod, 2, -1)
    x = _rms(x, params["ln_f"]["scale"].astype(jnp.float32)) \
        * (1 + scale_f[:, None]) + shift_f[:, None]
    out = _mm("bnd,dp->bnp", x, params["patch_out"], precision)
    out = out.reshape(b, gh, gw, p, p, ch).transpose(0, 1, 3, 2, 4, 5)
    return out.reshape(b, hh, ww, ch)


# --------------------------------------------------------------------------
# the sequential solve
# --------------------------------------------------------------------------

def ddpm_linear(num_steps: int, t_train: int = 1000, beta_start=1e-4,
                beta_end=0.02):
    """(alpha_bar, t_model) on the reversed grid (index 0 is pure noise):
    DDPM's linear betas, subsampled to ``num_steps`` intervals."""
    betas = np.linspace(beta_start, beta_end, t_train, dtype=np.float64)
    ab_full = np.cumprod(1.0 - betas)
    t_trad = np.round(np.linspace(t_train - 1, 0, num_steps + 1)).astype(
        np.int64)
    return (ab_full[t_trad].astype(np.float32),
            t_trad.astype(np.float32))


def initial_noise(seed: int, shape):
    """A request's initial noise: a standard normal drawn from its seed."""
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def ddim_sample(cfg, params, x, num_steps: int, precision="float32"):
    """The plain N-step deterministic DDIM solve from noise ``x``."""
    ab, t_model = ddpm_linear(num_steps)
    ab, t_model = jnp.asarray(ab), jnp.asarray(t_model)

    def step(i, x):
        a, b_ = ab[i], ab[i + 1]
        eps = forward(cfg, params, x, jnp.full((x.shape[0],), t_model[i]),
                      precision)
        x0 = (x - jnp.sqrt(1.0 - a) * eps) / jnp.sqrt(a)
        return jnp.sqrt(b_) * x0 + jnp.sqrt(1.0 - b_) * eps

    return jax.lax.fori_loop(0, num_steps, step, x)


def solve_all(cfg, params, seeds, num_steps: int, precision="float32",
              rows: int = 32) -> np.ndarray:
    """The reference samples of the requests with noise ``seeds``, in
    blocks of ``rows`` samples (one compiled shape; the last block is
    padded by repetition)."""
    seeds = list(seeds)
    shape = sample_shape(cfg)
    solve = jax.jit(functools.partial(ddim_sample, cfg,
                                      num_steps=num_steps,
                                      precision=precision))
    out = []
    for lo in range(0, len(seeds), rows):
        block = seeds[lo:lo + rows]
        padded = block + [block[-1]] * (rows - len(block))
        x = jnp.stack([initial_noise(s, shape) for s in padded])
        out.append(np.asarray(solve(params, x))[:len(block)])
    return np.concatenate(out) if out else np.zeros((0,) + shape, np.float32)
