"""A benchmark cell cut to a size the CPU tests can serve in seconds: a
configuration and a traffic mix from their files, by name, with small
widths, short requests and a short window."""

from __future__ import annotations

import json

from bench import harness

SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 256}
SETTINGS = {"rate_rps": 20.0,
            "check": {"rows": 8, "candidates": 24,
                      "limits": {"token_gap": 0.015,
                                 "prefill_logit_err": 0.025}},
            "trace": {"last_s": 0.5}}


def small_cell(config: str = "qwen2.5-3b", traffic: str = "chat",
               **deployment) -> harness.Cell:
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    base = json.loads((harness.BENCH / "configs" / f"{config}.json").read_text())
    mix = json.loads((harness.BENCH / "traffic" / f"{traffic}.json").read_text())
    cfg = {**base, **SMALL,
           "deployment": {**base["deployment"], "max_batch_rows": 8,
                          "row_quantum": 4, **deployment}}
    return harness.Cell(f"{config}.{traffic}", cfg,
                        {**mix, "prompt_len": 32, "gen": 8}, SETTINGS,
                        int(cfg["deployment"]["chips"]),
                        tuple(spec["end_to_end"]), tuple(spec["per_layer"]))
