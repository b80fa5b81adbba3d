"""Operation and byte counts against values worked by hand for OLMo-1B
(16 layers, d_model 2048, 16 heads of 128, MHA, d_ff 8192, vocab 50304)."""
import json
from pathlib import Path

import flops

BENCH = Path(__file__).resolve().parents[1]

OLMO = json.loads((BENCH / "configs" / "olmo-1b.json").read_text())

# per layer: q 2048*16*128 + k, v 2 * 2048*16*128 + o 16*128*2048
#            + SwiGLU 3 * 2048*8192 = 67,108,864; times 16 layers
MATMUL_PARAMS = 1_073_741_824


def test_matmul_params():
    assert flops.matmul_params(OLMO) == MATMUL_PARAMS


def test_decode_call():
    # two live rows at contexts 1000 and 1: each row 2 * (matmuls + LM head
    # 2048 * 50304); attention 4 * 16 layers * 16 heads * 128 * ctx
    per_row = 2 * (MATMUL_PARAMS + 2048 * 50304)
    assert per_row == 2_353_528_832
    attn = 4 * 16 * 16 * 128
    assert flops.decode_flops(OLMO, [1000, 1]) == \
        2 * per_row + attn * 1000 + attn * 1 == 4_838_260_736


def test_prefill_chunk():
    # 256 tokens at positions 256..511: keys 256*256 + 256*257/2 = 98,432
    assert flops.prefill_flops(OLMO, 256, 256) == \
        2 * MATMUL_PARAMS * 256 + 4 * 16 * 16 * 128 * 98_432 \
        == 562_657_492_992


def test_paged_attention_need():
    # ctx 1000 -> ceil(1000 / 16) = 63 pages per layer; a K+V page is
    # 2 * 16 tokens * 16 heads * 128 * 2 bytes = 131,072
    assert flops.kv_page_bytes(OLMO, 16) == 131_072
    ops, nbytes = flops.paged_attention_need(OLMO, [1000], 16)
    assert ops == 131_072_000
    assert nbytes == 16 * 63 * 131_072 == 132_120_576
    # a row at context 16 reads exactly one page per layer, 17 reads two
    assert flops.paged_attention_need(OLMO, [16], 16)[1] == 16 * 131_072
    assert flops.paged_attention_need(OLMO, [17], 16)[1] == 32 * 131_072
