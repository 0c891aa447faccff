"""Per-leaf gradients of the sharded step against the unsharded step.

The same parameters and batch go through ``make_grad_fn`` on one device
and under ``shard_map`` on a (data, model) mesh.  Each leaf's synchronized
mean gradient is compared with the one-device gradient, relative to its
norm.  A leaf whose gradient is counted once per model shard, or lost on
all but one, is off by a factor of 2 or more; the global grad norm hides
that on a small leaf.
"""
from __future__ import annotations

import jax
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import lm
from repro.models.params import tree_pspecs
from repro.train.trainer import make_grad_fn


def leaf_grad_check(cfg, params, batch, mesh) -> dict:
    """Compare the gradients of ``params`` (unsharded, on one device) on
    ``batch`` with those of the (data, model) ``mesh``.

    The one-device reference runs each data shard's slice of the batch and
    averages: MoE capacity and load-balance loss are per data shard by
    design, and for the other archs this is the whole-batch gradient.

    Returns ``loss1`` and ``loss`` (one device, mesh), per leaf path
    ``rel`` (``|g_mesh - g_1| / |g_1|``) and ``norm1`` (``|g_1|``), and
    ``split``: the leaves whose replicas on the mesh hold different
    gradients.
    """
    grad_fn = make_grad_fn(cfg)
    d, tp = mesh.shape["data"], mesh.shape["model"]
    n = len(next(iter(batch.values()))) // d
    g1 = jax.jit(grad_fn)
    parts = [g1(params, {k: v[h * n:(h + 1) * n] for k, v in batch.items()})
             for h in range(d)]
    loss1 = float(sum(float(l) for l, _ in parts)) / d
    grads1 = jax.device_get(jax.tree.map(
        lambda *g: sum(np.asarray(x, np.float64) for x in g) / d,
        *[g for _, g in parts]))
    del parts

    pspecs = tree_pspecs(lm.model_specs(cfg, tp=tp))
    params_m = jax.tree.map(
        lambda x, p: jax.device_put(x, NamedSharding(mesh, p)), params, pspecs)
    batch_m = {k: jax.device_put(np.asarray(v), NamedSharding(mesh, P("data")))
               for k, v in batch.items()}
    sm = shard_map(lambda p, b: (lambda l, g: (l[None], g))(*grad_fn(p, b)),
                   mesh=mesh,
                   in_specs=(pspecs, {k: P("data") for k in batch}),
                   out_specs=(P("data"), pspecs), check_vma=False)
    loss_m, grads_m = jax.jit(sm)(params_m, batch_m)
    del params_m

    rel, norm1, split = {}, {}, []
    for (path, a), b in zip(jax.tree.flatten_with_path(grads1)[0],
                            jax.tree.leaves(grads_m)):
        key = jax.tree_util.keystr(path)
        na = float(np.linalg.norm(a))
        diff = np.asarray(b, np.float64) - a
        rel[key] = float(np.linalg.norm(diff)) / max(na, 1e-30)
        norm1[key] = na
        held: dict[str, list] = {}
        for s in b.addressable_shards:
            held.setdefault(str(s.index), []).append(np.asarray(s.data))
        if any(not np.allclose(v[0], x, rtol=1e-6, atol=0)
               for v in held.values() for x in v):
            split.append(key)
    return {"loss1": loss1, "loss": float(np.mean(np.asarray(loss_m))),
            "rel": rel, "norm1": norm1, "split": split}
