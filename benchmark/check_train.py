"""What decides ``correct`` for a training cell.

The program's readings are taken by the driver from the object the
window then times (its first three steps).  Here: the plain reference
follows the same three steps from the same seed, and the two are
compared number by number, each against a limit of its own.

Numbers compared (names as they are printed):

- ``loss_gap_1..3``: |program's mean cross-entropy - reference's| over
  the reference's, each step;
- ``grad_norm_gap``: the first gradient as the optimizer gets it, by the
  worst leaf: |program's norm - reference's norm| over the reference's
  norm of that leaf or of the median leaf, whichever is larger;
- ``change_norm_gap``: the parameters' change over the three steps, by
  the same measure, over the leaves whose reference gradient is at
  least a thousandth of the median leaf's (the others move under Adam
  by round-off alone).
"""
import statistics

from benchmark.reference import optim


def named_norms(tree):
    """{leaf name: L2 norm} of a reference tree; stacked layers come
    back under the program's ``layer<i>_<leaf>`` names."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree):
        out = {}
        for k, v in tree.items():
            if k == "layers":
                for leaf, arr in v.items():
                    out[leaf] = jnp.sqrt(jnp.sum(
                        jnp.square(arr.reshape(arr.shape[0], -1)), axis=1))
            else:
                out[k] = jnp.sqrt(jnp.sum(jnp.square(v)))
        return out

    got = jax.device_get(norms(tree))
    flat = {}
    for k, v in got.items():
        if k in tree.get("layers", {}):
            for i, x in enumerate(v):
                flat[f"layer{i}_{k}"] = float(x)
        else:
            flat[k] = float(v)
    return flat


def reference_readings(family, config, recipe, seed, batches,
                       compute="f32", fault=None):
    """Three steps of the plain reference from the seed's weights.

    ``fault`` plants one of the faults the comparison has to catch, in
    the reference put in the program's place: ``"half_batch"`` (half of
    the rows left out, the rest weighed as the whole) or
    ``"state_unchanged"`` (every update thrown away)."""
    import jax
    import jax.numpy as jnp

    params = family.reference_params(config, seed)
    aux = family.reference_aux(config, seed) \
        if hasattr(family, "reference_aux") else None
    start = jax.tree_util.tree_map(jnp.copy, params) \
        if fault == "state_unchanged" else None
    opt = recipe["optimizer"]
    state = optim.init_state(params, opt)
    rescale = float(recipe.get("rescale_grad", 1.0))
    keep = None
    if fault == "half_batch":
        keep = len(family.labels_of(batches[0])) // 2
    losses, grad_norms = [], None
    for t, batch in enumerate(batches, start=1):
        loss, grads, aux = family.reference_grads(
            config, params, aux, batch, int(recipe.get("reference_rows", 2)),
            compute, keep=keep)
        if rescale != 1.0:
            grads = jax.tree_util.tree_map(lambda g: g * rescale, grads)
        losses.append(loss)
        if t == 1:
            grad_norms = named_norms(grads)
        lr_t = float(recipe["lr"])
        if opt == "adam":
            lr_t = optim.adam_lr(lr_t, t)
        params, state = optim.update(
            params, state, grads, lr_t, optimizer=opt,
            momentum=float(recipe.get("momentum", 0.0)))
        del grads
        if fault == "state_unchanged":
            params = jax.tree_util.tree_map(jnp.copy, start)
    del state
    first = family.reference_params(config, seed)
    change = named_norms(jax.tree_util.tree_map(jnp.subtract, params, first))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def _worst_gap(prog, ref, leaves):
    med = statistics.median(ref[k] for k in leaves)
    worst, where = 0.0, None
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def compare(prog, ref, limits):
    """[(name, value, limit)] and, for the log, which leaf was worst."""
    rows, notes = [], {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        # a step's loss may have a limit of its own (the first step's is
        # the forward pass alone and reads steadier than the later ones)
        rows.append((f"loss_gap_{i}", abs(a - b) / max(abs(b), 1e-30),
                     limits.get(f"loss_gap_{i}", limits.get("loss_gap"))))
    leaves = sorted(ref["grad_norms"])
    missing = [k for k in leaves if k not in prog["grad_norms"]]
    if missing:
        raise KeyError(f"the program reported no gradient for {missing[:4]}")
    gap, where = _worst_gap(prog["grad_norms"], ref["grad_norms"], leaves)
    rows.append(("grad_norm_gap", gap, limits["grad_norm_gap"]))
    notes["grad_norm_gap"] = where
    med = statistics.median(ref["grad_norms"].values())
    moved = [k for k in leaves if ref["grad_norms"][k] >= 1e-3 * med]
    notes["left_out_of_change"] = [k for k in leaves if k not in moved]
    gap, where = _worst_gap(prog["change_norms"], ref["change_norms"], moved)
    rows.append(("change_norm_gap", gap, limits["change_norm_gap"]))
    notes["change_norm_gap"] = where
    return rows, notes
