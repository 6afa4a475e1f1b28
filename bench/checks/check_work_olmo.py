"""The work functions (bench/work.py) against a hand count of OLMo-1B
(16 layers, d 2048, 16 query and 16 kv heads of 128, d_ff 8192, vocab
50304 tied, bf16, non-parametric LayerNorm):

  per layer   wq, wk, wv, wo 2048x2048 each = 16,777,216; gated MLP
              3 x 2048 x 8192 = 50,331,648; no norm gains
              -> 67,108,864; x 16 = 1,073,741,824 (1.07 B)
  per token   2 x 1,073,741,824 = 2,147,483,648 FLOPs (2.15 GFLOP) in the
              layers; attention adds 4 x 16 x 16 x 128 = 131,072 FLOPs per
              attended position; logits 2 x 2048 x 50304 = 206,045,184
  weights     the layers and the embedding table padded to 50,432 rows x
              2048, at 2 bytes, no final-norm gain: 2,354,053,120 B
              (2.35 GB), read once per decode step
  KV          2 x 16 x 16 x 128 x 2 = 131,072 B per position

    python3 bench/checks/check_work_olmo.py"""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import work  # noqa: E402


def main() -> int:
    m = json.loads((BENCH / "configs" / "olmo-1b.served.json").read_text()
                   )["model"]
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    want = {
        "layer_params": per_layer,
        "nonembed_params": 1_073_741_824,
        "weight_bytes": 2_354_053_120,
        "kv_bytes_per_token": 131_072,
        "embed_rows": 50_432,
        "attn_flops(1 position)": 131_072,
        "unembed_flops": 206_045_184,
        "decode_token_flops(ctx 999)": 2_147_483_648 + 206_045_184
        + 131_072 * 1000,
        "prefill_flops(3 new after 5)": 3 * 2_147_483_648
        + 131_072 * (6 + 7 + 8) + 206_045_184,
    }
    got = {
        "layer_params": work.layer_params(m),
        "nonembed_params": work.nonembed_params(m),
        "weight_bytes": work.weight_bytes(m),
        "kv_bytes_per_token": work.kv_bytes_per_token(m),
        "embed_rows": work.embed_rows(m),
        "attn_flops(1 position)": work.attn_flops(m, 1),
        "unembed_flops": work.unembed_flops(m),
        "decode_token_flops(ctx 999)": work.decode_token_flops(m, 999),
        "prefill_flops(3 new after 5)": work.prefill_flops(m, 3, 5),
    }
    ok = True
    for k in want:
        print(f"{k}: {got[k]} (hand count {want[k]})")
        ok &= got[k] == want[k]
    assert per_layer * 16 == 1_073_741_824
    assert 2 * (16 * per_layer + 50_432 * 2048) == 2_354_053_120
    f, b = work.decode_call_work(m, [100, 200], [2, 1])
    assert f == (work.decode_token_flops(m, 100) + work.decode_token_flops(m, 200)
                 + work.decode_token_flops(m, 101))
    assert b == (work.decode_step_bytes(m, [100, 200])
                 + work.decode_step_bytes(m, [101]))
    print("check_work_olmo:", "OK" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
