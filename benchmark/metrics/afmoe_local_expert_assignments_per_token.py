"""Assignments of a token to an expert held here, per token and EXPERT
layer (4 of the 5 layers route), over the whole run: the program's
counters ``local_expert_assignments`` over ``routed_tokens``, summed from
the round programs' own outputs as the host fetches each round's metrics.
Expected ``num_experts_per_tok * experts_held / num_experts`` (1.0 in the
AFMoE cell: 16 of 128 held, top-8); ``num_experts_per_tok`` would say
every expert was computed, 0 that the router sends nothing here."""

from harness import hostspans


def read(ctx):
    rec = hostspans.recorder()
    if rec is None:
        return None
    counters = rec.counters()
    tokens = counters.get("routed_tokens")
    if not tokens:
        return None
    return counters.get("local_expert_assignments", 0) / tokens
