"""The layer scan's rematerialisation (``models/lm.py:REMAT_POLICY``) keeps
the products with no batch dimension for the backward and recomputes the
rest: the train step's loss and gradients equal those of the step without
remat and of the step that recomputes every product, and in the compiled
step no dispatched projection (``pgtune.*``) is computed again under the
remat's second forward (``rematted_computation``), while the WKV and SSD
chunk products still are.  Tiny float32 configurations on a 1x1 mesh
through the Trainer, so that the projections go through the dispatcher."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import scopes
from bench.trace import hlo_op_names
from repro.configs import get_config
from repro.data import make_batch
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.train import Trainer

#: arch -> the batched chunk products' scope, recomputed under the remat
RECOMPUTED = {"rwkv6-3b": "wkv", "zamba2-1.2b": "ssd"}
#: float32 throughout: the saved products are the values the forward
#: computed, so only the compiler's order of sums differs
TOL = 1e-5
#: the gradients' gap to the step without remat.  In rwkv6 any remat,
#: the one that recomputes every product too, reads 5.7e-4: the WKV's
#: clamp ``exp(minimum(diff, 0))`` (``models/ssm.py:_wkv_scan``) is met
#: at a tie by the s = t - 1 terms, whose exponent is 0 in exact
#: arithmetic, so rounding picks each one's subgradient (1, 1/2 or 0),
#: and the two programs round otherwise.  Without the clamp the gap is
#: 1e-6.
TOL_NO_REMAT = {"rwkv6-3b": 1e-3, "zamba2-1.2b": TOL}


def _trainer(arch, remat=True):
    cfg = dataclasses.replace(get_config(arch).smoke(), dtype="float32",
                              remat=remat)
    return cfg, Trainer(cfg, mesh=make_host_mesh((1, 1), ("data", "model")),
                        warmup=1)


def _first_step(arch, remat=True):
    """Loss and every leaf's first gradient (AdamW's first moment after
    one step, over 1 - b1) of one step from the same weights."""
    cfg, tr = _trainer(arch, remat)
    params, opt = tr.init(0)
    batch = tr.put_batch(make_batch(cfg, 2, 32, 0))
    _, opt, m = tr.step(params, opt, batch, 0)
    return float(m["loss"]), [np.asarray(x) / 0.1
                              for x in jax.tree.leaves(opt["m"])]


def _worst_gap(grads, grads0):
    """The worst leaf's gap, against the larger of its norm and the
    median leaf's."""
    scale = np.median([np.linalg.norm(g) for g in grads0])
    return max(np.linalg.norm(g - g0) / max(np.linalg.norm(g0), scale)
               for g, g0 in zip(grads, grads0))


@pytest.mark.parametrize("arch", sorted(RECOMPUTED))
def test_remat_matches_no_remat(arch, monkeypatch):
    loss, grads = _first_step(arch)
    loss0, grads0 = _first_step(arch, remat=False)
    monkeypatch.setattr(lm, "REMAT_POLICY",
                        jax.checkpoint_policies.nothing_saveable)
    loss1, grads1 = _first_step(arch)
    assert np.isfinite(loss)
    assert abs(loss - loss0) <= TOL * abs(loss0)
    assert abs(loss - loss1) <= TOL * abs(loss1)
    assert _worst_gap(grads, grads0) <= TOL_NO_REMAT[arch]
    assert _worst_gap(grads, grads1) <= TOL


def _rematted_dots(arch):
    """The ``op_name``s of the compiled step's products, split into those
    under the remat's second forward and the rest."""
    cfg, tr = _trainer(arch)
    params, opt = tr.init(0)
    batch = tr.put_batch(make_batch(cfg, 2, 32, 0))
    with tr._tuned():
        text = tr._step.lower(params, opt, batch,
                              jnp.asarray(0, jnp.int32)).compile().as_text()
    dots = [scopes.scopes_of(s) for s in hlo_op_names(text).values()
            if s.rsplit("/", 1)[-1] == "dot_general"]
    return ([d for d in dots if "rematted_computation" in d],
            [d for d in dots if "rematted_computation" not in d])


def _dispatched(stack):
    return any(p.startswith("pgtune.") for p in stack)


@pytest.mark.parametrize("arch", sorted(RECOMPUTED))
def test_remat_recomputes_no_projection(arch):
    again, once = _rematted_dots(arch)
    assert any(_dispatched(d) for d in once)
    assert not [d for d in again if _dispatched(d)]
    assert any(RECOMPUTED[arch] in d for d in again)


def test_remat_reader_by_hand():
    """``remat_ms.train`` counts the instructions of the remat's second
    forward, and reads nothing in a step without a remat."""
    from bench import harness
    from bench import trace as T

    body = "jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint"
    names = {"fusion.1": body + "/rematted_computation/rwkv/wkv/dot_general",
             "fusion.2": body + "/rematted_computation/rwkv/mul",
             "fusion.3": body + "/rwkv/pgtune.matmul_accumulate.default/"
                                "dot_general",
             "fusion.4": "jit(step)/jvp(layers)/while/body/rwkv/dot_general"}
    cell = types.SimpleNamespace(scope_names=names)
    ops = T.leaf_ops([(0, 100, "fusion.1"), (200, 300, "fusion.2"),
                      (600, 50, "fusion.3"), (700, 40, "fusion.4")])
    read = harness.load_reader("remat_ms.train")

    def ctx(c):
        return harness.ReadContext(trace=T.Trace(ops={0: ops}, host=[],
                                                 window_s=1e-5),
                                   info={"steps": 2, "chips": 1}, cell=c,
                                   peaks=None)

    assert read(ctx(cell)) == pytest.approx((100 + 300) / 2 * 1e-6)
    bare = types.SimpleNamespace(scope_names={
        n: s.replace("/checkpoint/rematted_computation", "")
        for n, s in names.items()})
    assert read(ctx(bare)) is None
