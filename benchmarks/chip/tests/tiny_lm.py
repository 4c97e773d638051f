"""Tiny stand-in of the LM teacher cell for rehearsals on the CPU: the
real cell's files with the widths, vocabulary and batches cut to CPU size;
the cell's depth (a dense layer and 6 MoE layers), MLA without query LoRA,
YaRN, and the published routing (64 experts, top-6 and 2 shared), each
expert 16 wide."""
from __future__ import annotations

import tiny  # noqa: F401  (puts bench/ and src/ on the path)

TINY_LM = {"hidden_size": 64, "num_attention_heads": 2,
           "num_key_value_heads": 2, "kv_lora_rank": 32,
           "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "v_head_dim": 16,
           "intermediate_size": 128, "moe_intermediate_size": 16,
           "vocab_size": 512}
TINY_LM_TRAFFIC = {"seq_len": 32, "batch_seqs": 4, "pool_batches": 3,
                   "group_batches": 2, "sample_seqs": 2,
                   "sample_positions": 48}


def tiny_lm_cell():
    from bench import loader
    cell = loader.resolve("lm-teacher-gen")
    cell.config = dict(cell.config, **TINY_LM)
    cell.traffic = dict(cell.traffic, **TINY_LM_TRAFFIC)
    return cell
