"""Operations and bytes: the model's, per step, and each kernel call's.

Model FLOPs count the matrix multiplications a token needs (2 per
multiply-add) over the published vocabulary, not the padded one, plus
attention over the live context. Kernel counts are the least work the
call must do: ``decode_attn`` reads each live row's int8 K and V payload
and its scales once; a ``qmatmul``-family call reads each operand once and
writes its output once, with its operations from the operand shapes of
the compiled program.
"""

from __future__ import annotations

import re

_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "u8": 1, "s32": 4,
                "u32": 4, "pred": 1, "s16": 2, "f8e4m3fn": 1, "f8e5m2": 1}
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\([^()]*\)|\w+\[[\d,]*\])")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_CALL = re.compile(r"custom-call\(([^)]*)\)")
KERNELS = ("qmatmul_pallas", "qkv_pallas", "qmlp_pallas")


def _arrays(shape: str) -> list:
    return [(t, tuple(int(x) for x in d.split(",") if x))
            for t, d in _ARRAY.findall(shape)]


def _nbytes(arrays: list) -> int:
    total = 0
    for t, dims in arrays:
        n = 1
        for x in dims:
            n *= x
        total += n * _DTYPE_BYTES.get(t, 4)
    return total


def _strip(text: str) -> str:
    """An HLO line without its layouts (``{1,0:T(8,128)}``)."""
    return _LAYOUT.sub("", text)


def call_key(text: str) -> str:
    """Instruction name and output shape: what identifies a call both in
    the compiled program's text and in a trace event's name."""
    m = _DEF.match(_strip(text))
    return f"{m.group(1)}={m.group(2)}" if m else text


def kernel_calls(hlo_text: str) -> dict:
    """``call_key`` -> {kernel, flops, bytes, int8} for each Pallas call of
    the ``qmatmul`` family in one compiled program."""
    shapes, calls = {}, []
    for line in hlo_text.splitlines():
        line = _strip(line)
        m = _DEF.match(line)
        if not m:
            continue
        shapes[m.group(1)] = _arrays(m.group(2))
        kernel = re.sub(r"\.\d+$", "", m.group(1))
        if kernel in KERNELS and "custom-call(" in line:
            ops = [o.strip().lstrip("%") for o in
                   _CALL.search(line).group(1).split(",") if o.strip()]
            calls.append((m, kernel, ops))
    out = {}
    for m, kernel, ops in calls:
        outs = _arrays(m.group(2))
        args = [shapes[o][0] for o in ops if o in shapes]
        (xt, (mm, k)) = args[0]
        weights = [dims for t, dims in args[1:] if t == "s8" and len(dims) == 2]
        if kernel == "qmatmul_pallas":
            flops = 2 * mm * k * outs[0][1][-1]
        elif kernel == "qkv_pallas":
            flops = 2 * mm * k * sum(d[-1] for _, d in outs)
        else:  # qmlp: gate (optional) and up (F, K'), then down back to D
            f = weights[0][0]
            d = outs[0][1][-1]
            flops = 2 * mm * k * f * (len(weights) - 1) + 2 * mm * f * d
        out[f"{m.group(1)}={m.group(2)}"] = {
            "kernel": kernel, "flops": flops,
            "bytes": _nbytes(args) + _nbytes(outs),
            "int8": xt == "s8" and bool(weights)}
    return out


def roofline_s(flops: float, nbytes: float, peak_flops: float,
               bw: float) -> float:
    """The least time: the larger of the compute and the memory bound."""
    return max(flops / peak_flops, nbytes / bw)


def layer_matmul_params(conf: dict) -> int:
    d, h, kv, hd, f = (conf["d_model"], conf["num_heads"],
                       conf["num_kv_heads"], conf["head_dim"], conf["d_ff"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def token_matmul_flops(conf: dict) -> float:
    """2 x the matmul parameters one token goes through: every layer and
    the output head over the published vocabulary."""
    return 2.0 * (conf["num_layers"] * layer_matmul_params(conf)
                  + conf["vocab_size"] * conf["d_model"])


def attention_flops(conf: dict, rows: float) -> float:
    """QK and PV of one query against ``rows`` cached rows, all layers."""
    return 4.0 * conf["num_layers"] * conf["num_heads"] * conf["head_dim"] \
        * rows


def prefill_flops(conf: dict, p: int) -> float:
    """One prompt of ``p`` tokens: every token through every layer, the
    head for the last token only, causal attention (query i sees i+1
    rows)."""
    layers = 2.0 * conf["num_layers"] * layer_matmul_params(conf) * p
    head = 2.0 * conf["vocab_size"] * conf["d_model"]
    return layers + head + attention_flops(conf, p * (p + 1) / 2)


def decode_token_flops(conf: dict, rows: int) -> float:
    return token_matmul_flops(conf) + attention_flops(conf, rows)


def kv_row_bytes(conf: dict) -> float:
    """int8 K and V payload plus bf16 scales of one cached row, one layer."""
    feat = conf["num_kv_heads"] * conf["head_dim"]
    return 2 * (feat + feat // conf["kv_group"] * 2)


def decode_attn_call(conf: dict, rows: list) -> tuple:
    """(flops, bytes) of one decode step's attention over all layers, for
    slots attending ``rows`` cached rows each."""
    total = float(sum(rows))
    return (attention_flops(conf, total),
            conf["num_layers"] * kv_row_bytes(conf) * total)
