"""The MLA latent cache is stored flat: rows (..., S, r + rope) with no head
axis, raw or in a flat KVPage (quant/kvcache.py). These tests hold the flat
layout to the headed one it replaces, value for value: the stored bytes,
the writes, the admission of a prefilled request, decode attention, and
the tokens and logprobs served on the CPU paths. Caches with several kv
heads (yi's (..., 4, 128)) keep their head axes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels.decode_attn import ops as dops
from repro.models import mla as MLA
from repro.models.model import build
from repro.quant import kvcache as KV
from repro.serving.engine import ServeEngine
from repro.serving.scheduler import Request

L, B, S, F, GROUP = 3, 4, 40, 576, 64
PRECISIONS = ["bf16", "int8", "int4"]
CFG = get_config("moonlight-16b", smoke=True)


def _rows(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def _pages(precision):
    """The same raw latent cache as a flat page and as a headed one."""
    raw = _rows(0, (L, B, S, F))
    plan = KV.KVPlan((precision,) * L, group=GROUP)
    return (KV.quantize_cache_field(raw, plan),
            KV.quantize_cache_field(raw[..., None, :], plan))


def _headed_view(page):
    """A flat page (or raw (B, T, F) rows) seen with its head axis of one,
    as the cache was stored before: the same bytes, another shape."""
    if not isinstance(page, KV.KVPage):
        return page[:, :, None, :]
    data = page.data if page.precision == "int4" else page.data[..., None, :]
    return dataclasses.replace(page, data=data, flat=False)


def _page_list(field):
    return list(field) if isinstance(field, tuple) else [field]


def _same(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("precision", PRECISIONS)
def test_flat_page_stores_the_headed_bytes(precision):
    flat, headed = _pages(precision)
    assert flat.flat and not headed.flat
    assert flat.data.shape == ((L, B, S, F // 2) if precision == "int4"
                               else (L, B, S, F))
    _same(flat.data, headed.data.reshape(flat.data.shape))
    if precision != "bf16":
        _same(flat.scale, headed.scale)
    _same(KV.dequantize_kv(flat), KV.dequantize_kv(headed))


@pytest.mark.parametrize("precision", PRECISIONS)
def test_flat_page_writes_match_headed(precision):
    """Per-slot decode writes and the admission of a prefilled request
    store what the headed page stores."""
    flat, headed = _pages(precision)
    layer = lambda p: jax.tree.map(lambda x: x[1], p)
    new = _rows(1, (B, 1, F))
    pos = jnp.array([0, 7, S - 1, 19], jnp.int32)
    got = KV.update_page(layer(flat), new, pos)
    want = KV.update_page(layer(headed), new[:, :, None, :], pos)
    _same(got.data, want.data.reshape(got.data.shape))
    prompt = _rows(2, (L, 1, 11, F))
    got = KV.insert_slot(flat, prompt, 2)
    want = KV.insert_slot(headed, prompt[..., None, :], 2)
    _same(got.data, want.data.reshape(got.data.shape))
    if precision != "bf16":
        _same(got.scale, want.scale)


@pytest.mark.parametrize("precision", PRECISIONS)
def test_flat_page_heads_and_length(precision):
    """One kv head and S rows on the whole page, on a layer sliced off it,
    and on the layer the scan hands its body."""
    flat, _ = _pages(precision)
    assert (flat.num_kv_heads, flat.seq_len) == (1, S)
    one = jax.tree.map(lambda x: x[0], flat)
    assert one.flat and (one.num_kv_heads, one.seq_len) == (1, S)
    seen = []

    def body(c, page):
        seen.append((page.flat, page.num_kv_heads, page.seq_len))
        return c, None

    jax.lax.scan(body, 0, flat)
    assert seen == [(True, 1, S)]


def test_headed_pages_keep_their_layout():
    """yi-shaped K/V (4 kv heads of 128) is stored with its head axes, and
    the engines build each cache in its own layout."""
    raw = _rows(3, (L, B, S, 4, 128))
    for precision in PRECISIONS:
        page = KV.quantize_cache_field(raw, KV.KVPlan((precision,) * L))
        assert not page.flat
        assert page.data.shape[:3] == (L, B, S)
        assert (page.num_kv_heads, page.seq_len) == (4, S)
        one = jax.tree.map(lambda x: x[0], page)
        assert (one.num_kv_heads, one.seq_len) == (4, S)
    yi = build(get_config("yi-9b", smoke=True))
    eng = ServeEngine(yi, yi.init(jax.random.PRNGKey(0)), max_seq=32,
                      kv_precision="int8", autotune=False)
    for k in _page_list(eng.init_decode_state(2).cache.k):
        assert not k.flat and k.data.shape[1:] == (
            2, 32, yi.cfg.num_kv_heads, yi.cfg.head_dim)
    mla = build(CFG)
    eng = ServeEngine(mla, mla.init(jax.random.PRNGKey(0)), max_seq=32,
                      kv_precision="int8", autotune=False)
    pages = _page_list(eng.init_decode_state(2).cache.c)
    assert sum(c.data.shape[0] for c in pages) == CFG.num_layers
    for c in pages:
        assert c.flat and c.data.shape[1:] == (2, 32, CFG.latent_dim)


@pytest.mark.parametrize("backend", ["simple", "grouped"])
@pytest.mark.parametrize("precision", ["raw"] + PRECISIONS[1:])
def test_decode_attention_flat_matches_headed(backend, precision):
    """Absorbed MLA decode attention (heads over one latent kv head, V the
    same rows) reads the flat cache as it read the headed one, per-slot
    valid lengths and chunk edges included."""
    if precision == "raw":
        cache = _rows(4, (B, S, F)).astype(jnp.bfloat16)
    else:
        cache = jax.tree.map(lambda x: x[0], _pages(precision)[0])
    q = _rows(5, (B, 1, 8, F))
    valid = jnp.array([1, 17, S, 33], jnp.int32)
    kw = dict(valid_len=valid, backend=backend, kv_chunk=16)
    got = dops.decode_attention(q, cache, cache, **kw)
    view = _headed_view(cache)
    _same(got, dops.decode_attention(q, view, view, **kw))


def _serve(params, kv, backend):
    dops.configure_decode_attn(backend=backend)
    jax.clear_caches()
    eng = ServeEngine(build(CFG), params, max_seq=32, kv_precision=kv,
                      autotune=False)
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, CFG.vocab_size, n)
                    .astype(np.int32), max_new_tokens=m)
            for i, (n, m) in enumerate([(5, 7), (9, 3), (13, 9), (3, 5),
                                        (11, 6)])]
    outs, _ = eng.serve(reqs, num_slots=3, chunk=3)
    return ([o.tokens for o in outs], [o.logprobs for o in outs])


@pytest.mark.parametrize("backend", ["simple", "grouped"])
@pytest.mark.parametrize("kv", [None, "int8"])
def test_served_tokens_match_headed_view(monkeypatch, backend, kv):
    """Continuous batching (per-slot positions, slots released and
    reused) serves the same tokens and logprobs, bit for bit, whether
    decode attention reads the flat cache or its headed view."""
    was = dops.get_decode_attn_backend()
    params = build(CFG).init(jax.random.PRNGKey(0))
    try:
        tokens, logprobs = _serve(params, kv, backend)
        attend = MLA.decode_attention
        monkeypatch.setattr(
            MLA, "decode_attention",
            lambda q, k, v, **kw: attend(q, _headed_view(k),
                                         _headed_view(v), **kw))
        want_tokens, want_logprobs = _serve(params, kv, backend)
    finally:
        dops.configure_decode_attn(backend=was)
        jax.clear_caches()
    for got, want in zip(tokens + logprobs, want_tokens + want_logprobs):
        _same(got, want)


def test_slot_round_trip_through_release_and_reuse():
    """A prefilled request admitted into a slot lands there as its
    quantized rows; after the slot is released and given to another
    request, that request decodes exactly as in a slot never used."""
    model = build(CFG)
    eng = ServeEngine(model, model.init(jax.random.PRNGKey(0)), max_seq=32,
                      kv_precision="int8", autotune=False)
    rng = np.random.default_rng(2)
    a = eng.prefill_request(rng.integers(0, CFG.vocab_size, 9))
    b = eng.prefill_request(rng.integers(0, CFG.vocab_size, 5))

    def admitted(state, pf, n):
        # the insert program's fused division may round a value at a .5
        # edge the other way than an eager quantize: one step, rarely
        pages = _page_list(state.cache.c)
        data = jnp.concatenate([p.data[:, 1, :n] for p in pages])
        scale = jnp.concatenate([p.scale[:, 1, :n] for p in pages])
        want, want_scale = KV.quantize_kv(pf.cache.c[:, 0, :n], "int8",
                                          KV.DEFAULT_KV_GROUP, flat=True)
        diff = np.abs(np.asarray(data, np.int32) - np.asarray(want, np.int32))
        assert diff.max() <= 1 and diff.mean() < 1e-2
        _same(scale, want_scale)

    used = eng.insert(eng.init_decode_state(2), 1, a, 6)
    admitted(used, a, 9)
    used = eng.decode_chunk(used, 6)
    used = eng.release(used, 1)
    used = eng.insert(used, 1, b, 6)
    admitted(used, b, 5)
    fresh = eng.insert(eng.init_decode_state(2), 1, b, 6)
    used, fresh = eng.decode_chunk(used, 6), eng.decode_chunk(fresh, 6)
    assert int(used.lengths[1]) == 11
    _same(used.tokens[1], fresh.tokens[1])
    _same(used.logprobs[1], fresh.logprobs[1])
