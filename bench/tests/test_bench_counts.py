"""Operation and byte counters, against sums made by hand."""

import json
import pathlib

import pytest

from bench.harness import counts, spec

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _conf(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_kv_row_bytes_by_hand():
    # minicpm: 36 KV heads x 64 = 2304 int8 bytes, 2304/64 = 36 bf16 scales
    assert counts.kv_row_bytes(_conf("minicpm-2b")) == 2 * (2304 + 36 * 2)
    # yi: 4 x 128 = 512 bytes, 8 scales
    assert counts.kv_row_bytes(_conf("yi-9b-12L")) == 2 * (512 + 8 * 2)


@pytest.mark.parametrize("name,row", [("minicpm-2b", 4752),
                                      ("yi-9b-12L", 1056)])
def test_decode_attn_live_bytes(name, row):
    conf = _conf(name)
    rows = [300, 1025, 7]
    flops, nbytes = counts.decode_attn_call(conf, rows)
    assert nbytes == conf["num_layers"] * row * (300 + 1025 + 7)
    assert flops == 4 * conf["num_layers"] * conf["num_heads"] \
        * conf["head_dim"] * (300 + 1025 + 7)


def test_layer_params_by_hand():
    # yi: q 4096x4096, k and v 512x4096, o 4096x4096, mlp 3 x 4096x11008
    yi = _conf("yi-9b-12L")
    assert counts.layer_matmul_params(yi) == (
        4096 * 4096 * 2 + 512 * 4096 * 2 + 3 * 4096 * 11008)
    assert counts.token_matmul_flops(yi) == 2 * (
        12 * counts.layer_matmul_params(yi) + 64000 * 4096)


def test_prefill_flops_by_hand():
    c = {"num_layers": 1, "d_model": 4, "num_heads": 2, "num_kv_heads": 1,
         "head_dim": 2, "d_ff": 8, "vocab_size": 10}
    per_layer = 4 * 4 + 2 * 4 * 2 + 4 * 4 + 3 * 4 * 8   # 144
    p = 3
    attn = 4 * 1 * 2 * 2 * (1 + 2 + 3)                  # rows 1, 2, 3
    assert counts.prefill_flops(c, p) == 2 * per_layer * p + 2 * 10 * 4 \
        + attn


HLO = """
  %p0 = bf16[256,4096]{1,0:T(8,128)(2,1)} parameter(0)
  %w8 = s8[4096,4096]{1,0:T(8,128)(4,1)} parameter(1)
  %s8 = bf16[8,4,4096]{2,1,0} parameter(2)
  %w4 = s8[11008,2048]{1,0:T(8,128)(4,1)} parameter(3)
  %s4 = bf16[8,4,11008]{2,1,0} parameter(4)
  %wk = s8[512,4096]{1,0} parameter(5)
  %sk = bf16[8,4,512]{2,1,0} parameter(6)
  %qmatmul_pallas.3 = f32[256,4096]{1,0:T(8,128)} custom-call(%p0, %w8, %s8), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jit(qmatmul_pallas)/pallas_call"}
  %qmatmul_pallas.4 = f32[256,11008]{1,0:T(8,128)} custom-call(%p0, %w4, %s4), custom_call_target="tpu_custom_call"
  %qkv_pallas.5 = (f32[256,4096]{1,0:T(8,128)}, f32[256,512]{1,0}, f32[256,512]{1,0}) custom-call(%p0, %w8, %s8, %wk, %sk, %wk, %sk), custom_call_target="tpu_custom_call"
"""


def test_kernel_calls_from_hlo_shapes():
    calls = counts.kernel_calls(HLO)
    x = 256 * 4096 * 2
    int8 = calls["qmatmul_pallas.3=f32[256,4096]"]
    assert int8["flops"] == 2 * 256 * 4096 * 4096
    assert int8["bytes"] == x + 4096 * 4096 + 8 * 4 * 4096 * 2 \
        + 256 * 4096 * 4
    assert not int8["int8"]
    int4 = calls["qmatmul_pallas.4=f32[256,11008]"]
    assert int4["flops"] == 2 * 256 * 4096 * 11008
    assert int4["bytes"] == x + 11008 * 2048 + 8 * 4 * 11008 * 2 \
        + 256 * 11008 * 4
    qkv = calls["qkv_pallas.5=(f32[256,4096], f32[256,512], f32[256,512])"]
    assert qkv["flops"] == 2 * 256 * 4096 * (4096 + 512 + 512)
    # the key a trace event carries (layouts are dropped)
    event = ("%qkv_pallas.5 = (f32[256,4096]{1,0:T(8,128)}, f32[256,512]{1,0},"
             " f32[256,512]{1,0}) custom-call(%p0, %w8")
    assert counts.call_key(event) in calls


def test_roofline_is_the_larger_bound():
    assert counts.roofline_s(2e12, 1e9, 1e12, 1e9) == 2.0
    assert counts.roofline_s(1e9, 4e9, 1e12, 1e9) == 4.0


def test_peaks_table():
    pk = spec.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["int8_ops"] == 393e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v99")
