"""Operation and byte counts the benchmark divides by measured time, and
the table of chip peaks it divides by.

Model FLOPs per trained token follow the usual training count: 6 times the
parameters that take part in a matrix product per token (every layer's
projections, the LM head once whether tied or not; the embedding lookup
is no product), plus attention's ``12 * layers * d_attn * seq`` for the
score and value products of the forward and backward passes.
Recomputation (remat) is not counted.

The RMNP kernel reads its gradient, momentum and weight stripes and writes
momentum and weight; its bytes come from the launch's own operand and
result shapes in the compiled program, so a change of storage dtype moves
the count.  At one multiply-add per element against 16 bytes moved it is
memory-bound on every chip in the table: its least time is bytes over the
HBM bandwidth.
"""
from __future__ import annotations

import re
from typing import Dict, List, NamedTuple

# Published peaks per chip, keyed by JAX's ``device_kind``.  Source: Google
# Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s bf16,
# 16 GB HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; a chip missing from the table is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"add its published numbers to bench/flops.py")
    return PEAKS[device_kind]


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def matmul_params(model: Dict) -> int:
    """Parameters that enter a matrix product once per token: attention
    projections, the SwiGLU FFN's three matrices (its input matrix holds
    gate and up) and the LM head over the padded vocabulary."""
    d, heads = model["d_model"], model["n_heads"]
    hd = model.get("head_dim") or d // heads
    kv = model["n_kv_heads"]
    attn = d * heads * hd * 2 + d * kv * hd * 2      # q, o and k, v
    ffn = 3 * d * model["d_ff"]
    return model["num_layers"] * (attn + ffn) + d * padded_vocab(
        model["vocab"])


def flops_per_token(model: Dict, seq: int) -> float:
    """Model FLOPs of one trained token (forward and backward)."""
    d_attn = model["n_heads"] * (model.get("head_dim")
                                 or model["d_model"] // model["n_heads"])
    return (6.0 * matmul_params(model)
            + 12.0 * model["num_layers"] * d_attn * seq)


# --- kernel bytes from the compiled program -------------------------------

_ITEMSIZE = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
             "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
             "f8e4m3fn": 1, "f8e5m2": 1, "pred": 1, "s4": 0.5, "u4": 0.5}
_ARRAY = re.compile(r"\b(" + "|".join(sorted(_ITEMSIZE, key=len,
                                             reverse=True))
                    + r")\[([0-9,]*)\]")


class Launch(NamedTuple):
    name: str      # the HLO instruction, which is the trace event's name
    bytes: float   # operands read plus results written


def array_bytes(text: str) -> float:
    """Bytes of every ``dtype[d0,d1,...]`` array type in ``text``."""
    total = 0.0
    for dt, dims in _ARRAY.findall(text):
        n = 1
        for x in filter(None, dims.split(",")):
            n *= int(x)
        total += n * _ITEMSIZE[dt]
    return total


def kernel_launches(hlo_text: str, kernel: str) -> List[Launch]:
    """Every custom call of the compiled program named ``kernel`` (the
    Pallas kernel's ``name``; XLA numbers the instructions ``kernel.N``),
    with the bytes of its results and of its operands as the compiled
    program lays them out, per chip."""
    out = []
    pat = re.compile(r"%(" + re.escape(kernel) + r"(?:\.\d+)?) = (.*?) "
                     r"custom-call\(")
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if not m or "custom_call_target=" not in line:
            continue
        ops = _braced(line, "operand_layout_constraints={")
        if ops is None:
            raise ValueError(f"custom call {m.group(1)} names no operand "
                             f"layouts; cannot count its bytes")
        out.append(Launch(m.group(1), array_bytes(m.group(2))
                          + array_bytes(ops)))
    return out


def _braced(line: str, opener: str):
    """The text between ``opener`` (ending in ``{``) and its matching
    closing brace, or ``None``."""
    i = line.find(opener)
    if i < 0:
        return None
    start, depth = i + len(opener), 1
    for j in range(start, len(line)):
        depth += {"{": 1, "}": -1}.get(line[j], 0)
        if depth == 0:
            return line[start:j]
    return None
