"""A tiny cell defined only by data files, for tests on the CPU."""
from __future__ import annotations

import json
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def _stack(**kw) -> dict:
    base = dict(name="stack", family="dense", n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=0, head_dim=16,
                layer_pattern=["attention"], attention_kind="full",
                window_size=0, causal=True, rope_theta=10000.0, use_rope=True,
                activation="swiglu", ffn_pattern=["dense"], n_experts=0,
                top_k=0, ssm_d_state=16, ssm_d_conv=4, ssm_expand=2,
                rwkv_head_dim=64, input_embed_dim=0, has_lm_head=True,
                norm_eps=1e-5, tie_embeddings=False, logit_softcap=0.0,
                dtype="bfloat16", param_dtype="float32", remat=True,
                scan_layers=True)
    base.update(kw)
    return base


MODEL = {
    "name": "tiny-mllm",
    "encoder": _stack(name="tiny-enc", family="vlm-enc", causal=False,
                      use_rope=False, activation="gelu", input_embed_dim=32,
                      has_lm_head=False),
    "llm": _stack(name="tiny-llm", n_kv_heads=2, vocab_size=256,
                  rope_theta=1e6),
    "stub": {"modality": "vision", "n_tokens": 16, "embed_dim": 32},
    "connector_hidden": 64,
    "tokens_per_item_out": 4,
}

CONFIG = {"name": "tiny-1chip", "source": "test", "driver": "train",
          "reference": "mllm", "reduced": {}, "model": MODEL,
          "optimizer": {"lr": 1e-3, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                        "weight_decay": 0.1, "grad_clip": 1.0}}

TRAFFIC = {"mixture": {"single_image": 0.5, "multi_image": 0.5},
           "profiles": {"single_image": {"media": [1, 1], "text": [8, 32]},
                        "multi_image": {"media": [2, 4], "text": [16, 48]}},
           "items_per_step": 4,
           "microbatches": 2, "rows_per_microbatch": 2,
           "media_cap": 2, "text_cap": 48}

# Six seeds on the CPU read at most loss 0.0039, grad 0.0084, update 0.0023;
# the float8 control read at least loss 0.0199, grad 0.0366, update 0.0100,
# and half the batch loss 0.0675, grad 0.0321, update 0.0310 (three seeds).
LIMITS = {"loss_gap": {"limit": 0.012}, "grad_gap": {"limit": 0.02},
          "update_gap": {"limit": 0.006}}


def write_checkout(root: Path, limits: dict = LIMITS,
                   config: dict = CONFIG, chips: int = 1) -> str:
    """A checkout holding one tiny cell on ``chips`` chips, made of data
    files only; returns the cell's name."""
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "limits").mkdir()
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    name = config["name"]
    bench["configs"] = [{"name": name, "source": "test",
                         "file": f"bench/configs/{name}.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "tiny.mix", "config": name,
                           "traffic": "tinymix", "chips": chips, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / f"bench/configs/{name}.json").write_text(json.dumps(config))
    (root / "bench/traffic/tinymix.json").write_text(json.dumps(TRAFFIC))
    (root / "bench/limits/tiny.mix.json").write_text(json.dumps(limits))
    return "tiny.mix"
