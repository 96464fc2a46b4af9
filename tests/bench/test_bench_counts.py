"""The benchmark's yardsticks: model FLOPs per token, the RMNP kernel's
bytes from the compiled program, and the table of chip peaks."""
import json
from pathlib import Path

import pytest

from bench import flops

CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"

# gpt2-large block buckets (layers x d_in x d_out): attention q/k/v/o, FFN
# in (gate and up), FFN out
GPT2_LARGE_BUCKETS = [(144, 1280, 1280), (36, 1280, 10240), (36, 5120, 1280)]


def _model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def test_gpt2_large_flops_per_token_matches_hand_count():
    # 6 x 1.008e9 matmul parameters (tied head over the 50432 padded
    # vocabulary counted once) + 12 * 36 * 1280 * 1024 for attention
    hand = 6 * (36 * (4 * 1280 ** 2 + 3 * 1280 * 5120) + 1280 * 50432) \
        + 12 * 36 * 1280 * 1024
    got = flops.flops_per_token(_model("gpt2-large"), 1024)
    assert abs(got - hand) / hand < 1e-3
    assert abs(got - 6.616e9) / 6.616e9 < 1e-3


def test_phi3_counts_its_untied_head_once():
    m = _model("phi3-mini-8l")
    per_layer = 4 * 3072 ** 2 + 3 * 3072 * 8192
    assert flops.matmul_params(m) == 8 * per_layer + 3072 * 32256


def _custom_call(name, shape):
    """A compiled single-pass RMNP launch as the TPU compiler prints it:
    g, v (fp32) and w (bf16) in beside the SMEM scalars; v, w out."""
    dims = ",".join(map(str, shape))
    return (f"  %{name} = (f32[{dims}]{{2,1,0:T(8,128)}}, "
            f"bf16[{dims}]{{2,1,0:T(8,128)(2,1)}}) custom-call(%s, %g, %v, "
            f"%w), custom_call_target=\"tpu_custom_call\", "
            f"operand_layout_constraints={{f32[2]{{0}}, f32[{dims}]{{2,1,0}}, "
            f"f32[{dims}]{{2,1,0}}, bf16[{dims}]{{2,1,0}}}}, "
            f"metadata={{op_name=\"jit(step)/rmnp_rownorm_apply\"}}")


def test_kernel_bytes_are_16_per_parameter_at_gpt2_large_buckets():
    hlo = "\n".join([_custom_call(f"rmnp_rownorm_apply.{i}", s)
                     for i, s in enumerate(GPT2_LARGE_BUCKETS)]
                    + ["  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %p)"])
    launches = flops.kernel_launches(hlo, "rmnp_rownorm_apply")
    assert [ln.name for ln in launches] == [
        "rmnp_rownorm_apply.0", "rmnp_rownorm_apply.1",
        "rmnp_rownorm_apply.2"]
    for ln, (L, d_in, d_out) in zip(launches, GPT2_LARGE_BUCKETS,
                                    strict=True):
        # 16 bytes per parameter plus the two fp32 scalars
        assert ln.bytes == 16 * L * d_in * d_out + 8
    params = sum(L * a * b for L, a, b in GPT2_LARGE_BUCKETS)
    assert params == 943_718_400
    assert sum(ln.bytes for ln in launches) == 16 * params + 8 * 3


def test_kernel_bytes_follow_the_storage_dtype():
    line = _custom_call("rmnp_rownorm_apply", (4, 128, 256)).replace(
        "f32[4,128,256]", "bf16[4,128,256]")
    (ln,) = flops.kernel_launches(line, "rmnp_rownorm_apply")
    assert ln.bytes == 10 * 4 * 128 * 256 + 8


def test_peaks_of_a_known_chip_and_an_unknown_one_raises():
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        flops.peaks("TPU v9")
