"""Shrink a cell's files to a size the CPU runs in seconds: the same files,
the same code path, tiny widths and a short window. For rehearsals and the
tests under ``benchmark/tests`` only — the measuring path never shrinks."""

from __future__ import annotations

import copy

from benchmark.run import Cell


def tiny_cell(workload: str, rate_rps: float = 30.0) -> Cell:
    cell = Cell(workload)
    cfg = copy.deepcopy(cell.config)
    dc = cfg["program"]["decoder_config"]
    gqa = dc["num_kv_heads"] != dc["num_heads"]
    dc.update(vocab_size=512, d_model=64, num_layers=2, num_heads=4,
              num_kv_heads=2 if gqa else 4, mlp_dim=128,
              max_seq_len=256)
    # The reference reads the published keys: shrink them alike.
    for key, val in (("n_head", 4), ("num_attention_heads", 4),
                     ("num_key_value_heads", 2 if gqa else 4)):
        if key in cfg:
            cfg[key] = val
    if cfg.get("view") == "mimo":
        # State by layer kind and a rank's share of the experts: the widths
        # the common cut leaves (keys 192 / values 128, 8 window KV heads,
        # 16 held of 256 experts).
        dc.update(head_dim=24, v_head_dim=16, rope_dim=8, num_experts=8,
                  moe_top_k=2, moe_held_experts=4, dense_mlp_dim=96,
                  mlp_dim=32, sliding_kv_heads=4)
        cfg.update(head_dim=24, num_experts_per_tok=2)
    check = cfg["reference_check"]      # a long-context file's prompts
    if max(check["prompt_lens"]) + check["new_tokens"] > 256:   # max_len
        check["prompt_lens"] = [150, 200]
    cfg["program"]["register_as"] += "_tiny"
    llm = cfg["deployment"]["llm"]
    llm.update(num_slots=4, max_len=256, prompt_buckets=[32, 64],
               kv_pool_pages=8)
    traffic = copy.deepcopy(cell.traffic)
    long_prompts = traffic["prompt_len"]["lo"] >= 256
    traffic["prompt_len"].update(lo=80 if long_prompts else 8,
                                 hi=150 if long_prompts else 60)
    traffic["output_len"].update(lo=4, hi=12)
    if traffic["loop"] == "open":
        # a replica: every engine of several has scans to show in 3 s
        traffic["arrivals"]["rate_rps"] = rate_rps * int(
            cfg["deployment"]["num_replicas"])
        traffic["limits"] = {"ttft_ms": 60_000.0, "tpot_ms": 60_000.0}
    else:
        traffic.update(clients=4, set_size=8)
    traffic["preroll"] = 4
    cell.config, cell.traffic = cfg, traffic
    return cell
