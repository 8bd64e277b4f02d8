"""Rows a held routed expert computes a decode step, in each MoE layer:
the (row, expert) assignments of the decoded rows that landed on the
experts this chip holds, summed over the MoE layers, over the decode
steps, the experts held and the MoE layers.  Read from the program's
``serve.expert_rows`` span, one a decode step, whose metadata carries the
step's count, the experts held a layer and the MoE layers; a program
without it reads nothing."""

import program_spans as P


def read(ctx):
    found = P.named(ctx, "serve.expert_rows")
    if not found:
        return None
    total = sum(float(s.stats["assignments"]) for s in found)
    per_step = float(found[0].stats["experts"]) * float(found[0].stats["layers"])
    return total / len(found) / per_step
