"""Kernel block/tile autotuning seam: one home for every launch-shape knob.

Before this module existed, every Pallas kernel carried hardcoded tile
sizes (``TILE_ROWS = 256``, ``block_q = block_k = 128``, ``chunk = 32``)
that were never tuned for any backend.  The seam replaces those literals
with a three-tier resolution, keyed by ``(backend, kernel, dtype,
shape-bucket)``:

1. **explicit overrides** — a call site (or test) pins parameters via
   ``KernelTuner(overrides=...)`` / ``resolve(..., overrides=...)``;
2. **committed tuning tables** — versioned JSON under
   ``tuning_tables/<backend>.json``, written/refreshed by the measured
   sweep in ``benchmarks/autotune_kernels.py``;
3. **backend-aware heuristics** — the documented defaults (yesterday's
   constants become the CPU/interpret anchors; GPU gets Triton-sized
   tiles), used for any key the table does not cover.

``kernels.ops`` dispatch consults this module instead of literal
defaults; call sites outside ``repro.kernels`` must not pass raw tile
integers (reprolint RL010 ``kernel-tile-literals``) — they pass a
``tuner=`` or let dispatch resolve.  See docs/kernels.md for the
contract and the table-refresh procedure.

Tuned parameters per kernel family:

========== =============================== ==============================
kernel     parameters                      tuning shape (bucket basis)
========== =============================== ==============================
elementwise ``tile_rows``                  operand shape -> (total size,)
flash       ``block_q``, ``block_k`` (+    ``(sq, sk, head_dim)``
            ``num_warps``, ``num_stages``
            on the Triton lowering);
            ``block_b``, derived: the
            (sample, head) rows a forward
            grid step takes — 1 unless
            ``sq <= block_q`` and ``sk <=
            block_k``, else as many as fit
            ``FLASH_STEP_VMEM_BYTES``
            (:func:`flash_block_b`)
rwkv6       ``chunk_target`` (TPU chunked  ``(t, dk)``
            grid; the GPU kernel streams
            timesteps and ignores it)
========== =============================== ==============================

Buckets round every dimension up to the next power of two, so a handful
of table entries covers a continuum of shapes; a miss falls back to the
heuristic tier (never an error).  A *malformed* table, by contrast,
fails loudly (:class:`TuningTableError`) — a silently ignored table is
how a tuned deployment quietly runs default sizes.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Mapping, Optional, Sequence, Tuple

from .elementwise import TILE_ROWS

__all__ = [
    "KernelConfig", "KernelTuner", "TuningTableError", "TABLE_SCHEMA_VERSION",
    "TABLE_DIR", "KERNELS", "bucket_for", "next_pow2", "get_tuner",
    "set_tuner", "resolve", "pick_chunk", "sample_tile_rows",
    "flash_block_b", "pick_block_b", "FLASH_STEP_VMEM_BYTES",
    "validate_table",
]

TABLE_SCHEMA_VERSION = 1
TABLE_DIR = os.path.join(os.path.dirname(__file__), "tuning_tables")
KERNELS = ("elementwise", "flash", "rwkv6")
_SOURCES = ("override", "table", "heuristic")

# Backend-aware heuristic defaults — tier (3).  The ``None`` row is the
# fallback for CPU/interpret and any unknown backend: it carries the
# constants the kernels shipped with (elementwise.TILE_ROWS, the MXU-sized
# 128x128 flash tiles, the chunk=32 WKV grid), which stay the documented
# interpret-mode anchors.  The GPU row is Triton-sized: a (256, 128) f32
# elementwise tile is 128 KiB — past shared-memory budgets — so row tiles
# shrink; flash tiles drop to 64x64 with explicit warp/stage counts.
_HEURISTICS: Dict[str, Dict[Optional[str], Dict[str, int]]] = {
    "elementwise": {
        "tpu": {"tile_rows": TILE_ROWS},
        "gpu": {"tile_rows": 32},
        None: {"tile_rows": TILE_ROWS},
    },
    "flash": {
        "tpu": {"block_q": 128, "block_k": 128},
        "gpu": {"block_q": 64, "block_k": 64, "num_warps": 4,
                "num_stages": 2},
        None: {"block_q": 128, "block_k": 128},
    },
    "rwkv6": {
        "tpu": {"chunk_target": 32},
        "gpu": {"chunk_target": 32},
        None: {"chunk_target": 32},
    },
}


# VMEM one grid step of the batched (one-tile) flash forward may plan for,
# well inside v5e's 16 MiB default scoped VMEM.  It gives 46 rows at
# (64, 64, 64), so 24 of a DiT call's 48: on a v5e the fastest of 12, 16,
# 24 and 48 rows a step, all within 5% of each other.
FLASH_STEP_VMEM_BYTES = 8 << 20


def flash_block_b(shape: Optional[Sequence[int]], block_q: int,
                  block_k: int) -> int:
    """The ``block_b`` heuristic: how many (sample, head) rows one flash
    forward grid step may take at tuning shape ``(sq, sk, head_dim)``.

    1 whenever the sequence spans more than one tile (the per-head launch
    stays).  On one tile a grid step costs a fixed ~0.5 µs on a v5e while
    a 64x64 head's math takes nanoseconds, so as many rows as fit
    :data:`FLASH_STEP_VMEM_BYTES` at 4-byte words a row: 4·(sq+sk)·d for
    the double-buffered q, o, k and v blocks and 3·sq·sk for the scores,
    the probabilities and a temporary."""
    if shape is None:
        return 1
    sq, sk, d = (int(x) for x in shape)
    if sq > block_q or sk > block_k:
        return 1
    per_row = 4 * (4 * (sq + sk) * d + 3 * sq * sk)
    return max(1, FLASH_STEP_VMEM_BYTES // per_row)


def pick_block_b(rows: int, group: int, cap: int) -> int:
    """Rows a batched flash step takes: the largest multiple of the GQA
    ``group`` that divides ``rows`` (= batch * q heads) and is at most
    ``cap`` (the resolved ``block_b``); 1 (the per-head launch) when
    ``cap`` is below one group."""
    if cap < max(group, 2):
        return 1
    return group * _largest_divisor(rows // group, cap // group)


class TuningTableError(ValueError):
    """A tuning table failed validation — raised loudly, never skipped."""


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """A resolved kernel launch configuration.

    ``source`` records provenance for benchmarking/CI: ``"override"``
    (an explicit parameter won), ``"table"`` (a committed tuning-table
    entry matched the full key) or ``"heuristic"`` (backend-aware
    default).  ``key`` is the ``(backend, kernel, dtype, bucket)``
    lookup that produced it.
    """
    kernel: str
    params: Mapping[str, int]
    source: str
    key: Tuple[str, str, str, Tuple[int, ...]]


def next_pow2(n: int) -> int:
    """Smallest power of two >= ``n`` (>=1)."""
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


def bucket_for(kernel: str, shape: Optional[Sequence[int]]) -> Tuple[int, ...]:
    """Shape bucket for a kernel's tuning shape (see module docstring).

    ``elementwise`` buckets on total element count (the op flattens);
    the others bucket per dimension.  ``None`` -> the empty bucket
    (matches only entries with ``"bucket": []``, i.e. shape-agnostic).
    """
    if shape is None:
        return ()
    dims = [int(d) for d in shape]
    if kernel == "elementwise":
        total = 1
        for d in dims:
            total *= max(1, d)
        return (next_pow2(total),)
    return tuple(next_pow2(d) for d in dims)


def _largest_divisor(n: int, cap: int) -> int:
    for c in range(min(int(cap), int(n)), 0, -1):
        if n % c == 0:
            return c
    return 1


def pick_chunk(t: int, cap: int = 32) -> int:
    """Largest divisor of the sequence length ``t`` not exceeding ``cap``
    (the chunked WKV grid needs ``t % chunk == 0``).  ``cap`` comes from
    the resolved ``rwkv6`` config's ``chunk_target``."""
    return _largest_divisor(t, cap)


def sample_tile_rows(rows: int, cap: int) -> int:
    """Largest divisor of the per-sample row count not exceeding ``cap``
    (tile rows must divide ``rows`` so per-tile reduction partials stay
    sample-local).  ``cap`` comes from the resolved ``elementwise``
    config's ``tile_rows``."""
    return _largest_divisor(rows, cap)


def validate_table(obj, path: str = "<table>") -> dict:
    """Validate a tuning-table payload; returns it or raises loudly."""
    def bad(msg):
        raise TuningTableError(f"tuning table {path}: {msg}")

    if not isinstance(obj, dict):
        bad(f"top level must be an object, got {type(obj).__name__}")
    if obj.get("version") != TABLE_SCHEMA_VERSION:
        bad(f"version must be {TABLE_SCHEMA_VERSION}, "
            f"got {obj.get('version')!r} (refresh the table with "
            f"benchmarks.autotune_kernels)")
    if not isinstance(obj.get("backend"), str):
        bad("missing/non-string 'backend'")
    entries = obj.get("entries")
    if not isinstance(entries, list):
        bad("'entries' must be a list")
    for i, e in enumerate(entries):
        where = f"entries[{i}]"
        if not isinstance(e, dict):
            bad(f"{where} must be an object")
        if e.get("kernel") not in KERNELS:
            bad(f"{where}: unknown kernel {e.get('kernel')!r} "
                f"(known: {KERNELS})")
        if not isinstance(e.get("dtype"), str):
            bad(f"{where}: missing/non-string 'dtype'")
        bucket = e.get("bucket")
        if not isinstance(bucket, list) or not all(
                isinstance(b, int) and not isinstance(b, bool) and b > 0
                for b in bucket):
            bad(f"{where}: 'bucket' must be a list of positive ints")
        params = e.get("params")
        if not isinstance(params, dict) or not params or not all(
                isinstance(k, str) and isinstance(v, int)
                and not isinstance(v, bool) and v > 0
                for k, v in params.items()):
            bad(f"{where}: 'params' must be a non-empty "
                f"{{name: positive int}} object")
    return obj


def _dtype_name(dtype) -> str:
    if dtype is None:
        return "float32"
    if isinstance(dtype, str):
        return dtype
    import jax.numpy as jnp
    return jnp.dtype(dtype).name


class KernelTuner:
    """Resolves kernel launch parameters from overrides > tables > heuristics.

    Args:
      table_dir: directory of per-backend ``<backend>.json`` tables
        (default: the committed ``tuning_tables/``).  A missing file is
        a valid empty table; a malformed file raises
        :class:`TuningTableError` at first resolve for that backend.
      tables: pre-built ``{backend: payload}`` tables (validated here),
        taking precedence over ``table_dir`` files — the in-memory path
        used by tests and the autotune sweep's self-check.
      overrides: ``{kernel: {param: int}}`` pinned parameters applied on
        top of whatever the table/heuristic tier resolves.
    """

    def __init__(self, table_dir: Optional[str] = None,
                 tables: Optional[Mapping[str, dict]] = None,
                 overrides: Optional[Mapping[str, Mapping[str, int]]] = None):
        self.table_dir = TABLE_DIR if table_dir is None else table_dir
        self.overrides = {k: dict(v) for k, v in (overrides or {}).items()}
        self._tables: Dict[str, Optional[dict]] = {}
        for backend, payload in (tables or {}).items():
            self._tables[backend] = validate_table(
                payload, f"<tables[{backend!r}]>")

    def _table(self, backend: str) -> Optional[dict]:
        if backend not in self._tables:
            path = os.path.join(self.table_dir, f"{backend}.json")
            if not os.path.exists(path):
                self._tables[backend] = None
            else:
                try:
                    with open(path) as f:
                        payload = json.load(f)
                except (OSError, json.JSONDecodeError) as e:
                    raise TuningTableError(
                        f"tuning table {path}: unreadable/invalid JSON "
                        f"({e})") from e
                self._tables[backend] = validate_table(payload, path)
        return self._tables[backend]

    def _lookup(self, backend: str, kernel: str, dtype: str,
                bucket: Tuple[int, ...]) -> Optional[Dict[str, int]]:
        table = self._table(backend)
        if table is None:
            return None
        for e in table["entries"]:
            if (e["kernel"] == kernel and e["dtype"] == dtype
                    and tuple(e["bucket"]) == bucket):
                return dict(e["params"])
        return None

    def resolve(self, kernel: str, *, backend: Optional[str] = None,
                dtype=None, shape: Optional[Sequence[int]] = None,
                overrides: Optional[Mapping[str, int]] = None) -> KernelConfig:
        """Resolve launch parameters for ``kernel``.

        ``backend=None`` probes ``jax.default_backend()``; ``shape`` is
        the kernel's tuning shape (see module docstring), bucketed
        before lookup.  An unknown ``(dtype, bucket)`` key falls back to
        the backend heuristics; overrides (instance-level, then
        call-level) always win and mark the config ``source="override"``.
        """
        if kernel not in KERNELS:
            raise ValueError(f"unknown kernel {kernel!r} (known: {KERNELS})")
        if backend is None:
            import jax
            backend = jax.default_backend()
        dt = _dtype_name(dtype)
        bucket = bucket_for(kernel, shape)
        heur = _HEURISTICS[kernel]
        params = dict(heur.get(backend) or heur[None])
        source = "heuristic"
        from_table = self._lookup(backend, kernel, dt, bucket)
        if from_table is not None:
            params.update(from_table)
            source = "table"
        pinned = dict(self.overrides.get(kernel) or {})
        pinned.update(overrides or {})
        if pinned:
            params.update(pinned)
            source = "override"
        if kernel == "flash" and "block_b" not in params:
            params["block_b"] = flash_block_b(shape, params["block_q"],
                                              params["block_k"])
        return KernelConfig(kernel=kernel, params=params, source=source,
                            key=(backend, kernel, dt, bucket))


_DEFAULT_TUNER: Optional[KernelTuner] = None


def get_tuner() -> KernelTuner:
    """The process-default tuner (committed tables + heuristics)."""
    global _DEFAULT_TUNER
    if _DEFAULT_TUNER is None:
        _DEFAULT_TUNER = KernelTuner()
    return _DEFAULT_TUNER


def set_tuner(tuner: Optional[KernelTuner]) -> None:
    """Install (or with ``None`` reset) the process-default tuner."""
    global _DEFAULT_TUNER
    _DEFAULT_TUNER = tuner


def resolve(kernel: str, **kwargs) -> KernelConfig:
    """``get_tuner().resolve(...)`` convenience."""
    return get_tuner().resolve(kernel, **kwargs)
