"""Routed assignments per held expert, MoE layer and decode step in the
window: the program's counter `lm_moe_held_assignments` (per MoE layer)
over the held experts and the counter `lm_moe_steps`, both read as
differences across the window.  Even routing reads slots * top_k / router
experts (32 * 6 / 64 = 3 in the DeepSeek-V2 cell)."""


def read(run):
    held, steps = run.get("moe_held_assignments"), run.get("moe_steps")
    if not held or not steps:
        return None
    return held / run["held_experts"] / steps
