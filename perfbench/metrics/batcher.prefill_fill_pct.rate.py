"""Prompt tokens the window's admissions brought, as a share of the padded
rows x bucket their prefill programs ran."""
from perfbench import timeline


def read(ctx):
    return timeline.fill_pct(ctx, "engine.prefill_tokens_real", "engine.prefill_tokens_run")
