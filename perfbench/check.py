"""The comparison that decides ``correct``: each number against a
limit of its own (from the cell's workload file, set from chip readings
that PERF.md lists).  A number that is missing, or not finite, fails."""
import math
import statistics


def worst_leaf_gap(prog, ref, skip=()):
    """The gap between the program's norm and the reference's, by the
    worst leaf, against the reference's norm of that leaf or of the
    median leaf, whichever is larger.  Returns (gap, leaf)."""
    median = statistics.median(ref.values())
    worst, where = 0.0, None
    for leaf, r in ref.items():
        if leaf in skip or leaf not in prog:
            continue
        gap = abs(prog[leaf] - r) / max(r, median)
        if not gap <= worst:        # also catches nan
            worst, where = gap, leaf
    return worst, where


def still_leaves(ref_grad_norms, share=1e-3):
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's): Adam moves them by round-off
    alone, so their change is not compared."""
    median = statistics.median(ref_grad_norms.values())
    return {leaf for leaf, g in ref_grad_norms.items() if g < share * median}


MIN_LEAF = 16


def train_numbers(prog, ref, sizes):
    """``prog`` and ``ref``: {"losses", "grad_norms", "change_norms"} of
    the same first steps; ``sizes``: elements of each leaf.  Returns
    ({number: value}, {number: leaf}).  The gradient's gap leaves out
    leaves of fewer than ``MIN_LEAF`` elements: the norm of the
    next-sentence bias's two numbers is one difference of nearly equal
    means, and its noise hid every other leaf (PERF.md, section 2)."""
    numbers, where = {}, {}
    for k, (p, r) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        numbers[f"loss{k}_gap"] = abs(p - r) / abs(r)
    numbers["grad_gap"], where["grad_gap"] = worst_leaf_gap(
        prog["grad_norms"], ref["grad_norms"],
        skip={leaf for leaf, n in sizes.items() if n < MIN_LEAF})
    numbers["change_gap"], where["change_gap"] = worst_leaf_gap(
        prog["change_norms"], ref["change_norms"],
        skip=still_leaves(ref["grad_norms"]))
    return numbers, where


def verdict(numbers, limits):
    """(correct, {name: [value, limit]}): every limit's number has to be
    there, finite and within it."""
    table, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        ok = ok and good
        table[name] = [value if value is None or math.isfinite(value)
                       else repr(value), limit]
    return ok, table
