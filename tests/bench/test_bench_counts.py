"""bench/counts.py and bench/peaks.py against hand arithmetic."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import counts, peaks  # noqa: E402


class Leaf:
    """Shape and dtype, like a ``jax.ShapeDtypeStruct``."""

    def __init__(self, shape, dtype="float32"):
        self.shape, self.dtype = tuple(shape), np.dtype(dtype)


def smollm_dense():
    L, D, Q, KV, F, V = 32, 960, 15 * 64, 5 * 64, 2560, 49152
    lin = lambda a, b: {"w": Leaf((L, a, b))}  # noqa: E731
    return {"embed": Leaf((V, D)), "final_norm": {"scale": Leaf((D,))},
            "decoder": {"run0": {
                "ln1": {"scale": Leaf((L, D))}, "ln2": {"scale": Leaf((L, D))},
                "attn": {"wq": lin(D, Q), "wk": lin(D, KV), "wv": lin(D, KV),
                         "wo": lin(Q, D)},
                "mlp": {"w_gate": lin(D, F), "w_up": lin(D, F),
                        "w_down": lin(F, D)}}}}


def test_smollm_dense_param_bytes_at_bf16():
    # per layer: q 960*960 + k,v 2*960*320 + o 960*960 + 3*960*2560
    # + two norms 2*960 = 9,600,000 + 1,920; embedding 49152*960;
    # final norm 960
    per_layer = 960 * 960 * 2 + 960 * 320 * 2 + 3 * 960 * 2560 + 2 * 960
    n = 32 * per_layer + 49152 * 960 + 960
    assert n == 361_821_120
    tree = smollm_dense()
    assert counts.param_count(tree) == n
    # stored float32, computed bfloat16: 2 bytes each
    assert counts.param_bytes(tree, "bfloat16") == 2 * n
    assert counts.param_bytes(tree, "float32") == 4 * n


def test_narrower_of_stored_and_compute_dtype():
    assert counts.served_itemsize("float32", "bfloat16") == 2
    assert counts.served_itemsize("int8", "bfloat16") == 1
    assert counts.served_itemsize("bfloat16", "float32") == 2
    tree = {"a": Leaf((10, 4), "float32"), "b": Leaf((6,), "int8")}
    assert counts.param_bytes(tree, "bfloat16") == 40 * 2 + 6 * 1


def test_factorized_tree_flops_and_bytes_share_the_basis_once():
    B = Leaf((8, 3))                      # shared basis of a 2-member group
    tree = {"embed": Leaf((50, 8)),
            "layers": [{"q": {"B": B, "C": Leaf((3, 16))}},
                       {"q": {"B": B, "C": Leaf((3, 16))}},
                       {"o": {"w": Leaf((16, 8))}}]}
    # each member applies B (8*3) then its C (3*16); o is dense 16*8;
    # tied head 50*8; 2 FLOPs per multiply-add
    want = 2 * (2 * (8 * 3 + 3 * 16) + 16 * 8 + 50 * 8)
    assert counts.linear_flops_per_token(tree, tied_head=True) == want
    assert counts.linear_flops_per_token(tree, tied_head=False) == \
        want - 2 * 50 * 8
    # bytes: B once, two C, o, embedding; bf16
    assert counts.param_bytes(tree, "bfloat16") == \
        2 * (8 * 3 + 2 * 3 * 16 + 16 * 8 + 50 * 8)


def test_kv_bytes_over_live_lengths():
    # SmolLM-360M: 32 layers x K and V x 5 heads x 64 x 2 bytes = 40 KiB
    # per position; two slots at a mean of 64 live positions
    full = [0] * 32
    assert counts.kv_bytes(2, 64, full, 5, 64, "bfloat16") == \
        128 * 32 * 2 * 5 * 64 * 2
    assert counts.kv_bytes(0, 64, full, 5, 64, "bfloat16") == 0
    # a mean that is no whole number: the slots' positions, rounded
    assert counts.kv_bytes(3, 10.5, full, 5, 64, "bfloat16") == \
        32 * 32 * 2 * 5 * 64 * 2
    # three window-16 layers to one full one: the window layers read 16
    # positions of each slot, the full one all 64
    mixed = [16, 16, 16, 0] * 2
    assert counts.kv_bytes(2, 64, mixed, 4, 32, "bfloat16") == \
        2 * 4 * 32 * 2 * (6 * 2 * 16 + 2 * 2 * 64)
    # a context inside the window is read whole
    assert counts.kv_bytes(2, 10, mixed, 4, 32, "bfloat16") == \
        counts.kv_bytes(2, 10, [0] * 8, 4, 32, "bfloat16")


def test_attention_flops_and_roofline_bound():
    assert counts.attention_flops(10, [0, 0], 3, 4) == 4 * 10 * 3 * 4 * 2
    # one layer windowed at 4: it reads 4 positions, the other 10
    assert counts.attention_flops(10, [4, 0], 3, 4) == 4 * (4 + 10) * 3 * 4
    # a prompt of 6: token i reads i positions (1 + ... + 6 = 21), or at
    # most 4 under the window (1 + 2 + 3 + 4 + 4 + 4 = 18)
    assert counts.prompt_attention_flops(6, [0, 0], 3, 4) == \
        4 * 21 * 3 * 4 * 2
    assert counts.prompt_attention_flops(6, [4, 0], 3, 4) == \
        4 * (18 + 21) * 3 * 4
    assert counts.prompt_attention_flops(3, [4], 3, 4) == 4 * 6 * 3 * 4
    t, bound = counts.roofline_seconds(1e12, 1e9, 1e14, 1e12)
    assert (t, bound) == (pytest.approx(1e-2), "compute")
    t, bound = counts.roofline_seconds(1e9, 1e9, 1e14, 1e12)
    assert (t, bound) == (pytest.approx(1e-3), "memory")


def test_peaks_known_and_unknown_kinds():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v99")
