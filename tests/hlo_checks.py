"""View-sized ops of a compiled pooled decode program, split by the branch
of its sparse-method fallback cond (test_page_pool.py on the CPU,
test_chip_compile.py for a described v5e)."""
import re

import numpy as np

from repro.launch import hlo_walk

# the DSA decode step: the dense fallback gathers K and V, the sparse
# branch only K (the index projection); no view is copied head-major
DSA_VIEW_OPS = {"dense_gathers": 2, "other_gathers": 1, "other_copies": 0,
                "head_major": 0}


def reached(comps, roots):
    """Names of the computations ``roots`` call, directly or not."""
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for ins in comps[name].instrs:
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                               ins.rest)
            m = re.search(r"branch_computations=\{([^}]*)\}", ins.rest)
            if m:
                todo += [b.strip().lstrip("%") for b in m.group(1).split(",")]
    return seen


def view_ops(hlo, pool_shape, B, NP):
    """Counts of the view-sized ops of a decode program with a sparse
    fallback cond: gathers in the dense fallback's branch and elsewhere,
    copies outside the dense branch, and results of the head-major shape
    [B, NP, KV, ps, dh]."""
    _, _, ps, kv, dh = pool_shape
    view = B * NP * ps * kv * dh
    comps = hlo_walk.parse_computations(hlo)
    dense = set()
    for c in comps.values():
        for ins in c.instrs:
            m = re.search(r"branch_computations=\{%?([\w.\-]+),", ins.rest)
            if ins.op == "conditional" and m:          # branch 0: the false,
                dense |= reached(comps, [m.group(1)])  # dense, branch
    found = dict.fromkeys(DSA_VIEW_OPS, 0)
    for c in comps.values():
        for ins in c.instrs:
            m = re.match(r"\w+\[([\d,]*)\]", ins.result_spec)
            dims = [int(d) for d in m.group(1).split(",") if d] if m else []
            if dims == [B, NP, kv, ps, dh]:
                found["head_major"] += 1
            if int(np.prod(dims)) != view:
                continue
            if ins.op == "gather":
                found["dense_gathers" if c.name in dense
                      else "other_gathers"] += 1
            elif ins.op == "copy" and c.name not in dense:
                found["other_copies"] += 1
    return found
