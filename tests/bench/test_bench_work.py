"""The step_mfu work function against a hand count, one case per pass
kind, at a reduced configuration."""
import pytest

from bench import work

M = {"n_layers": 4, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
     "head_dim": 4, "d_ff": 16, "vocab_size": 10}
ES = {"stage_layers": [0, 1], "keep": [2, 1]}
BLOCK = 4


def hand_layer(ctx):
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up/down 8x16 each; attention QK^T
    # and PV over ctx positions for 2 heads of 4
    mm = 8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 3 * 8 * 16
    return 2 * mm + 2 * 2 * ctx * 2 * 4


@pytest.mark.parametrize("kind,prompt,blocks,expect", [
    # 6 prompt tokens + 2 blocks of 4: 14 positions through 4 layers,
    # the head on the block's 4 rows
    ("prompt_refresh", 6, 2, 4 * 14 * hand_layer(14) + 4 * 2 * 8 * 10),
    # the block's 4 positions through 4 layers at context 14
    ("block_refresh", 6, 2, 4 * 4 * hand_layer(14) + 4 * 2 * 8 * 10),
    # 4 positions through layer 0, 2 through layer 1, 1 through layers 2-3;
    # the head on the last kept position
    ("skip_decode", 6, 2, (4 + 2 + 1 + 1) * hand_layer(14) + 1 * 2 * 8 * 10),
])
def test_step_flops_by_hand(kind, prompt, blocks, expect):
    assert work.step_flops(M, ES, kind, prompt, blocks, BLOCK) == expect


def test_rows_per_layer_and_unknown_kind():
    assert work.rows_per_layer(M, ES, BLOCK) == [4, 2, 1, 1]
    with pytest.raises(ValueError):
        work.step_flops(M, ES, "vanilla", 1, 1, BLOCK)
