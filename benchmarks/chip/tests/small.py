"""Small configurations for the CPU tests: a configuration file shrunk to
its program's ``.reduced()`` widths, and a hook that makes ``run.py``
drive it."""

from __future__ import annotations

import dataclasses


def shrink(cfg: dict, pcfg, **over):
    """(file dict, program config) at the program's ``.reduced()`` sizes."""
    p = pcfg.reduced(**over)
    small = dict(cfg, hidden_size=p.d_model, intermediate_size=p.d_ff,
                 num_attention_heads=p.num_heads,
                 num_key_value_heads=p.num_kv_heads, head_dim=p.head_dim,
                 num_hidden_layers=p.num_layers, vocab_size=p.vocab_size,
                 num_local_experts=p.num_experts,
                 num_experts_per_tok=p.experts_per_token,
                 sliding_window=p.sliding_window)
    return small, p


def hook(seq_len: int = 64, calib_tasks: int = 24, check_requests: int = 4,
         **over):
    """A ``run.main`` driver hook: shrink the cell's model and traffic."""
    def apply(run):
        run.cfg, run.pcfg = shrink(run.cfg, run.pcfg, **over)
        run.mix = dict(run.mix, seq_len=seq_len, calib_tasks=calib_tasks,
                       check_requests=check_requests, check_block=2)
        if "rate_rps" in run.mix:
            run.mix["rate_rps"] = 8.0
    return apply


def dropless(pcfg):
    return dataclasses.replace(pcfg, capacity_factor=float(pcfg.num_experts)) \
        if pcfg.num_experts else pcfg
