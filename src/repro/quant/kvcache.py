"""Entropy-weighted quantized KV cache (docs/DESIGN.md §10).

The serving KV cache dominates decode memory at ``num_slots x max_seq`` and
is re-read in full every token step. ``KVPage`` extends the paper's
layer-level entropy argument from weights to that cache: each attention
layer's K/V buffers are stored int8 or packed int4 with per-group scales,
the per-layer precision chosen by a ``KVPlan`` (uniform, or derived from
the layer's existing entropy decision — quant/compiler.compile_kv_plan).

Layout
------
A page covers a contiguous run of cache layers at ONE precision:

  data  : (L?, B, S, Hkv, hd)      int8   ("int8")
          (L?, B, S, F // 2)       int8   ("int4", two nibbles per byte,
                                           stored FLAT over F = Hkv * hd)
          (L?, B, S, Hkv, hd)      bf16   ("bf16", scale is None)
          (L?, B, S, F)            int8 | bf16  (``flat``: one kv head)
  scale : (L?, B, S, F // group)   bf16   — F = Hkv * hd, groups along the
          FLATTENED head axis so small head dims still amortize one bf16
          scale over ``group`` elements (bytes/slot stays ~bits/8 per elem).

A cache with ONE kv head — MLA's latent rows, F = r + rope — is stored
``flat``: no head axis at all, its rows written, sliced and read as
(B, S, F). A tiled size-1 axis in the payload made XLA relayout the whole
cache between the layer scan's slice, the per-slot write and the
kernel's (B, S, F) view, several times a layer and step. The choice
is made from the shape the page is made from: a raw stacked field of
rank 4, (L, B, S, F), is flat; (L, B, S, Hkv, hd) keeps its heads. It is
kept as static page metadata because under the layer scan's slicing the
rank alone cannot tell (B, S, Hkv, hd) from (L, B, S, F).

int4 pages drop the (Hkv, hd//2) head split in storage: the packed payload
keeps the flat F/2 axis as its minor dimension. The bytes are identical
(row-major (Hkv, hd//2) and F/2 coincide) but the SHAPE matters to XLA's
CPU fallback codegen: elementwise nibble/convert loops over a minor
dimension of hd//2 (32 for hd=64) de-vectorize to ~4x the cost of the
same ops over an F/2-wide minor axis, which made int4 decode pay ~2x over
int8 despite reading half the bytes. ``dequantize_kv`` restores the
(Hkv, hd) head split only on its OUTPUT, after the hot unpack/scale ops.

Pages are registered pytrees, so they ride through jit / lax.scan (the
leading layer axis is scanned over exactly like a raw stacked cache) and
through ``serving/batch.DecodeState`` as the decode-loop carry.

Quantize-on-insert invariant: prefill runs in bf16; K/V enter a page only
through ``update_page`` (per-token decode write) or ``insert_slot``
(admitting a prefilled request into a slot), both of which quantize at the
write. The steady-state carry of the jitted decode scan is therefore
always quantized — decode never holds a bf16 copy of the cache.

Mixed per-layer plans cut the cache into a tuple of pages whose boundaries
are forced to the parameter-stack segment boundaries (``cuts``), so page i
lines up 1:1 with ``quant.apply.segment_slices`` segment i and each model
scan sees a single-precision page.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_KV_GROUP = 64
KV_PRECISIONS = ("bf16", "int8", "int4")


@dataclasses.dataclass(frozen=True)
class KVPlan:
    """Per-cache-layer precision plan for a family's KV cache.

    ``precisions`` carries one entry per element of the cache's leading
    layer axis (L decoder layers; U shared-attention sites for hybrid).
    ``group`` is the scale-group size along the flattened (Hkv*hd) axis.
    """
    precisions: tuple[str, ...]
    group: int = DEFAULT_KV_GROUP

    def __post_init__(self):
        for p in self.precisions:
            if p not in KV_PRECISIONS:
                raise ValueError(f"unknown KV precision {p!r}; "
                                 f"one of {KV_PRECISIONS}")

    def pages(self, cuts: Sequence[int] = ()) -> list[tuple[str, int, int]]:
        """Maximal equal-precision runs, additionally cut at ``cuts`` (the
        parameter-stack segment boundaries) so pages align 1:1 with the
        segments the model scans."""
        cutset = set(cuts)
        runs: list[tuple[str, int, int]] = []
        start = 0
        n = len(self.precisions)
        for i in range(1, n + 1):
            if (i == n or self.precisions[i] != self.precisions[start]
                    or i in cutset):
                runs.append((self.precisions[start], start, i))
                start = i
        return runs

    def to_dict(self) -> dict:
        return {"precisions": list(self.precisions), "group": self.group}

    @staticmethod
    def from_dict(d: dict) -> "KVPlan":
        return KVPlan(precisions=tuple(d["precisions"]), group=int(d["group"]))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVPage:
    """One contiguous run of cache layers at a single precision.

    Shapes are derived from ``data`` (not static metadata) so pages stay
    valid under scan/vmap slicing of the leading layer axis; only
    ``flat`` (one kv head stored without its axis) is static.
    """
    data: Any                 # see module docstring
    scale: Any                # bf16 per-group scales, or None for "bf16"
    precision: str            # static
    head_dim: int             # static logical hd (int4 stores hd//2 bytes)
    group: int                # static, divides Hkv*hd
    flat: bool = False        # static: one kv head, rows (..., S, hd)

    def tree_flatten(self):
        return (self.data, self.scale), (self.precision, self.head_dim,
                                         self.group, self.flat)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scale = children
        precision, head_dim, group, flat = aux
        return cls(data=data, scale=scale, precision=precision,
                   head_dim=head_dim, group=group, flat=flat)

    @property
    def num_kv_heads(self) -> int:
        if self.flat:
            return 1
        if self.precision == "int4":    # flat (..., F // 2) payload
            return 2 * self.data.shape[-1] // self.head_dim
        return self.data.shape[-2]

    @property
    def seq_len(self) -> int:
        # rows sit ahead of (Hkv, hd), or of the flat (F or F // 2) axis
        rows_last = self.flat or self.precision == "int4"
        return self.data.shape[-2 if rows_last else -3]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKV:
    """Pool-backed paged layout of a KV cache field (docs/DESIGN.md §13).

    Instead of a dense per-slot (B, S_max) reservation, tokens live in a
    shared pool of physical pages of ``page_size`` tokens each, reached
    through a per-slot page table:

      data  : (L?, N, P, Hkv, hd)  int8 | float   pool payload
              (L?, N, P, F // 2)   int8           ("int4", packed flat)
      scale : (L?, N, P, F//group) bf16, or None  per-group scales
      table : (L?, B, n_log)       int32          slot -> physical page

    N = pool_pages + 1: physical page 0 is the sacrificial DUMP page — it
    is never allocated, and every released / unallocated table entry points
    at it, so writes from inactive slots land on garbage instead of
    corrupting a reallocated page (reads past ``valid_len`` are masked by
    the decode kernels, so the garbage is never observed).

    The table broadcasts over the same leading layer axis as the payload,
    so scan/vmap slicing of the layer axis (hybrid's per-unit scan, the
    draft's kv_take_layers) slices every leaf uniformly. "bf16"-precision
    pools store the raw cache dtype verbatim (no bf16 rounding), keeping
    the paged bf16 engine numerically identical to the dense raw path.
    """
    data: Any
    scale: Any
    table: Any
    precision: str            # static
    head_dim: int             # static logical hd (int4 stores hd//2 bytes)
    group: int                # static, divides Hkv*hd
    page_size: int            # static tokens per physical page
    # pools hold K/V with its head axes: a one-head latent cache is served
    # from dense slots only (the engine refuses a pool for it)
    flat = False

    def tree_flatten(self):
        return (self.data, self.scale, self.table), (
            self.precision, self.head_dim, self.group, self.page_size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, scale, table = children
        precision, head_dim, group, page_size = aux
        return cls(data=data, scale=scale, table=table, precision=precision,
                   head_dim=head_dim, group=group, page_size=page_size)

    @property
    def num_kv_heads(self) -> int:
        if self.precision == "int4":    # flat (..., F // 2) payload
            return 2 * self.data.shape[-1] // self.head_dim
        return self.data.shape[-2]

    @property
    def seq_len(self) -> int:
        """Logical sequence capacity a slot's page table addresses."""
        return self.table.shape[-1] * self.page_size

    @property
    def num_pages(self) -> int:
        """Physical pool pages including the dump page."""
        axis = -3 if self.precision == "int4" else -4
        return self.data.shape[axis]


def is_kv_page(x: Any) -> bool:
    """True for a KVPage/PagedKV or a (non-empty) tuple of them."""
    if isinstance(x, (KVPage, PagedKV)):
        return True
    return (isinstance(x, tuple) and len(x) > 0
            and all(isinstance(p, (KVPage, PagedKV)) for p in x))


# ---------------------------------------------------------------------------
# quantize / dequantize (flat-head grouping)
# ---------------------------------------------------------------------------

def quantize_kv(x: jax.Array, precision: str, group: int,
                flat: bool = False
                ) -> tuple[jax.Array, Optional[jax.Array]]:
    """x: (..., Hkv, hd) float, or a flat page's (..., F) rows -> (data,
    scale) in the page layout."""
    heads = x.shape[-1:] if flat else x.shape[-2:]
    lead = x.shape[:x.ndim - len(heads)]
    if precision == "bf16":
        return x.astype(jnp.bfloat16), None
    f = int(np.prod(heads))
    assert f % group == 0, f"Hkv*hd={f} not divisible by kv group {group}"
    # groups over the flattened heads
    g = x.astype(jnp.float32).reshape(*lead, f // group, group)
    absmax = jnp.max(jnp.abs(g), axis=-1, keepdims=True)
    qmax = 127.0 if precision == "int8" else 7.0
    scale = absmax / qmax
    q = jnp.round(g / jnp.where(scale == 0, 1.0, scale))
    q = jnp.clip(q, -qmax, qmax).astype(jnp.int8)
    scale = scale[..., 0].astype(jnp.bfloat16)
    if precision == "int8":
        return q.reshape(*lead, *heads), scale
    if precision == "int4":
        assert heads[-1] % 2 == 0, \
            f"int4 KV packing needs an even head dim, {heads[-1]}"
        # split-half packing over the flat F axis: byte j holds flat
        # elements j (low nibble) and j + F/2 (high nibble), so the unpack
        # on the decode hot path is a single concat — no interleave
        # shuffle. Stored FLAT (..., F//2): see the module docstring.
        flat_q = q.reshape(*lead, f)
        half = f // 2
        packed = ((flat_q[..., :half] & 0x0F)
                  | ((flat_q[..., half:] & 0x0F) << 4)).astype(jnp.int8)
        return packed, scale
    raise ValueError(f"cannot quantize KV to {precision!r}")


def _unpack_kv_int4(data: jax.Array) -> jax.Array:
    """(..., P) packed -> (..., 2P): low nibbles are flat elements [0, P),
    high nibbles [P, 2P) (split-half layout — see ``quantize_kv``). The
    low nibble sign-extends with xor/sub (no select); the high nibble via
    int8 arithmetic right-shift — one op each."""
    lo = ((data & 0x0F) ^ 8) - 8
    hi = data >> 4
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.int8)


def dequantize_kv(page: KVPage, dtype=jnp.float32) -> jax.Array:
    """Page -> (..., Hkv, hd) in ``dtype`` (bf16 pages: a plain cast); a
    flat page gets its one head axis back on the output only."""
    if page.precision == "bf16":
        out = page.data.astype(dtype)
        return out[..., None, :] if page.flat else out
    data = page.data
    if page.precision == "int4" or page.flat:
        # work over the stored flat F axis (int4: unpacked from F/2 first);
        # every op here runs with the wide F/2 (then F) minor dimension —
        # the head split is restored only on the output reshape below
        if page.precision == "int4":
            data = _unpack_kv_int4(data)                  # (..., F) int8
        *lead, f = data.shape
        g = data.astype(jnp.float32).reshape(*lead, f // page.group,
                                             page.group)
        out = g * page.scale.astype(jnp.float32)[..., None]
        return out.reshape(*lead, f // page.head_dim,
                           page.head_dim).astype(dtype)
    *lead, hkv, hd = data.shape
    g = data.astype(jnp.float32).reshape(*lead, hkv * hd // page.group,
                                         page.group)
    out = g * page.scale.astype(jnp.float32)[..., None]
    return out.reshape(*lead, hkv, hd).astype(dtype)


def make_page(raw: jax.Array, precision: str, group: int,
              flat: bool = False) -> KVPage:
    """Quantize a raw (..., S, Hkv, hd) cache buffer, or a one-head
    cache's (..., S, F) rows with ``flat``, into one page."""
    data, scale = quantize_kv(raw, precision, group, flat)
    return KVPage(data=data, scale=scale, precision=precision,
                  head_dim=raw.shape[-1], group=group, flat=flat)


# ---------------------------------------------------------------------------
# page writes (quantize-on-insert)
# ---------------------------------------------------------------------------

def update_page(page, new: jax.Array, pos: jax.Array):
    """Decode-step write: quantize ``new`` (B, s, Hkv, hd), or (B, s, F)
    for a flat page, and store it at sequence position ``pos`` (scalar, or
    (B,) per-slot vector). Paged fields scatter through the slot's page
    table instead (quant/paged.py)."""
    if isinstance(page, PagedKV):
        from repro.quant import paged
        return paged.update_pages(page, new, pos)
    data_n, scale_n = quantize_kv(new, page.precision, page.group,
                                  page.flat)
    data_n = data_n.astype(page.data.dtype)

    def write(dst, src, p):
        if getattr(p, "ndim", 0) == 1:  # per-slot positions
            return jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
                c, n, (i,) + (0,) * (c.ndim - 1)))(dst, src, p)
        start = (jnp.int32(0), p) + (0,) * (dst.ndim - 2)
        return jax.lax.dynamic_update_slice(dst, src, start)

    data = write(page.data, data_n, pos)
    scale = (None if scale_n is None
             else write(page.scale, scale_n.astype(page.scale.dtype), pos))
    return dataclasses.replace(page, data=data, scale=scale)


def _page_lengths(field) -> list[int]:
    pages = field if isinstance(field, tuple) else (field,)
    return [p.data.shape[0] for p in pages]


def insert_slot(field, src: jax.Array, slot) -> Any:
    """Admit a prefilled request: quantize the raw batch=1 cache ``src``
    ((L, 1, S, Hkv, hd), or (L, 1, S, F) for flat pages) into slot
    ``slot`` of a slotted page field (batch axis 1). ``field`` is a KVPage
    or tuple of KVPages over layer runs."""
    pages = field if isinstance(field, tuple) else (field,)
    out, lo = [], 0
    for page in pages:
        hi = lo + page.data.shape[0]
        data_n, scale_n = quantize_kv(src[lo:hi], page.precision, page.group,
                                      page.flat)

        def write(dst, new):
            start = (0, slot) + (0,) * (dst.ndim - 2)
            return jax.lax.dynamic_update_slice(dst, new.astype(dst.dtype),
                                                start)

        out.append(dataclasses.replace(
            page, data=write(page.data, data_n),
            scale=None if scale_n is None else write(page.scale, scale_n)))
        lo = hi
    return tuple(out) if isinstance(field, tuple) else out[0]


# ---------------------------------------------------------------------------
# model-cache conversion and per-segment access helpers
# ---------------------------------------------------------------------------

def quantize_cache_field(raw: jax.Array, plan: KVPlan,
                         cuts: Sequence[int] = ()) -> Any:
    """Raw stacked (L, B, S, Hkv, hd) cache buffer, or a one-head cache's
    (L, B, S, F) rows (stored flat), -> page container.

    Single-run plans yield a bare KVPage; mixed plans a tuple of pages cut
    at ``cuts`` so page i aligns with parameter segment i."""
    runs = plan.pages(cuts)
    assert runs and runs[-1][2] == raw.shape[0], \
        (f"KV plan covers {runs[-1][2] if runs else 0} layers; cache has "
         f"{raw.shape[0]}")
    flat = raw.ndim == 4
    pages = tuple(make_page(raw[lo:hi], prec, plan.group, flat)
                  for prec, lo, hi in runs)
    return pages if len(pages) > 1 else pages[0]


def quantize_model_cache(cache, plan: KVPlan, cuts: Sequence[int],
                         fields: Sequence[str]):
    """Replace each named KV field of a family cache with quantized pages
    (no-op for families without attention caches)."""
    reps = {}
    for name in fields:
        raw = getattr(cache, name)
        if is_kv_page(raw):
            reps[name] = raw  # already quantized
        else:
            reps[name] = quantize_cache_field(raw, plan, cuts)
    return cache._replace(**reps) if reps else cache


def kv_segment(field, si: int, lo: int, hi: int):
    """Slice a cache field for parameter segment ``si`` covering layers
    [lo, hi). Quantized fields are page-aligned 1:1 with segments."""
    if isinstance(field, tuple):
        page = field[si]
        assert page.data.shape[0] == hi - lo, \
            (f"KV page {si} holds {page.data.shape[0]} layers; segment "
             f"[{lo},{hi}) expects {hi - lo} — cache pages must be built "
             f"with the parameter segmentation's cuts")
        return page
    if isinstance(field, (KVPage, PagedKV)):
        assert si == 0, "single-page cache with a multi-segment stack"
        return field
    return field[lo:hi]


def kv_rejoin(field, parts: list):
    """Rebuild a cache field from per-segment scan outputs, preserving the
    original container type."""
    if isinstance(field, tuple):
        return tuple(parts)
    if isinstance(field, (KVPage, PagedKV)):
        return parts[0]
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]


def kv_take_layers(field, lo: int, hi: int):
    """Read-only slice of cache layers [lo, hi) from any container (raw
    stack, single page, page tuple). Unlike ``kv_segment`` the range does
    not have to BE a page — it only has to sit INSIDE one. The fused draft
    propose path iterates the DRAFT's segments, which refine the target
    segmentation the pages were cut at (quant/compiler.compile_draft_plan
    preserves boundaries; truncation only shortens the last segment), so
    single-page coverage is guaranteed by construction."""
    if isinstance(field, tuple):
        plo = 0
        for page in field:
            phi = plo + page.data.shape[0]
            if plo <= lo and hi <= phi:
                return jax.tree.map(lambda x: x[lo - plo:hi - plo], page)
            plo = phi
        raise ValueError(
            f"layer range [{lo},{hi}) straddles KV page boundaries "
            f"(page lengths {_page_lengths(field)}) — draft segments must "
            f"refine the segmentation the cache pages were cut at")
    if isinstance(field, (KVPage, PagedKV)):
        return jax.tree.map(lambda x: x[lo:hi], field)
    return field[lo:hi]


def kv_layer(field, i: int):
    """Index one layer/site of a cache field (hybrid's unrolled units)."""
    if isinstance(field, (KVPage, PagedKV)):
        return jax.tree.map(lambda x: x[i], field)
    assert not isinstance(field, tuple), \
        "per-layer indexing expects a single-page (uniform) hybrid cache"
    return field[i]


def kv_stack(field, parts: list):
    """Stack per-layer results back into the original container layout."""
    if isinstance(field, (KVPage, PagedKV)):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *parts)
    return jnp.stack(parts)


def kv_field_nbytes(field) -> float:
    """Physical bytes of a cache field (pages count data + scales)."""
    total = 0.0
    for leaf in jax.tree.leaves(field):
        total += float(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total
