"""Device time per train step that the expert layers' scan over the
passes spends outside its three inner scopes: `moe.experts` less
(`moe.gather` + `moe.products` + `moe.combine`), i.e. what the loop and
the conditionals cost by themselves (the carries handed through every
pass, run or skipped, copies, zero fills, the sums of weight gradients
into the carry).  The compiler's `ragged-dot-*` calls, which carry no
scope, are the products' on both sides of the difference.  None unless
all four scopes are in the trace."""

from perfbench.harness import scopes

WHOLE = r"moe\.experts|ragged-dot-.*"
INNER = (r"moe\.gather", r"moe\.products|ragged-dot-.*", r"moe\.combine")


def read(run):
    if scopes.ms_per_step(run, r"moe\.products") is None:
        return None
    whole = scopes.ms_per_step(run, WHOLE)
    parts = [scopes.ms_per_step(run, p) for p in INNER]
    if whole is None or None in parts:
        return None
    return whole - sum(parts)
