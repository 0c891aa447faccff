"""The scope readers on a small synthetic trace whose answers are counted
by hand: an instruction counts for a scope when a component of its name
stack, autodiff's wrappers taken off, is the scope; a program without the
scope, or a trace without device ops, reads nothing."""
import types

import pytest

from bench import harness, scopes
from bench import trace as T

# op_name of each instruction of a compiled step (as hlo_op_names gives it)
NAMES = {
    "fusion.1": "jit(step)/jit(main)/jvp()/while/body/closed_call/rwkv/wkv/"
                "while/body/dot_general",
    "fusion.2": "jit(step)/jit(main)/transpose(jvp())/while/body/"
                "closed_call/rwkv/wkv/while/body/add_any",
    "fusion.3": "jit(step)/jit(main)/jvp()/while/body/closed_call/rwkv/"
                "pgtune.matmul_accumulate.default/dot_general",
    "fusion.4": "jit(step)/jit(main)/jvp(head)/dot_general",
    "fusion.5": "jit(step)/jit(main)/transpose(jvp(head))/dot_general",
    "fusion.6": "jit(step)/jit(main)/optimizer/add",
    "copy.7": "jit(step)/jit(main)/transpose(jvp())/dynamic_update_slice",
}


class FakeCell:
    """What the readers ask of a train driver: the step's names (here
    given, as ``op_names`` keeps them once built) and the trainer's
    recompile count."""

    def __init__(self, names=NAMES, recompiles=0):
        self.scope_names = dict(names)
        self.trainer = types.SimpleNamespace(recompiles=recompiles)


def _ctx(cell=None, steps=2, ops=None):
    # chip 0: a while container and its body; chip 1 runs the same ops
    # for other lengths.  ns: wkv 100 + 300, rwkv 50, head 40 + 60,
    # optimizer 80, unscoped 30 and 20 (not in the module)
    ops0 = T.leaf_ops([(0, 2000, "while.9"), (0, 100, "fusion.1"),
                       (200, 300, "fusion.2"), (600, 50, "fusion.3"),
                       (700, 40, "fusion.4"), (800, 60, "fusion.5"),
                       (900, 80, "fusion.6"), (1000, 30, "copy.7"),
                       (1100, 20, "copy.99")])
    ops1 = T.leaf_ops([(0, 200, "fusion.1"), (300, 100, "fusion.2"),
                       (500, 100, "fusion.6")])
    tr = T.Trace(ops={0: ops0, 1: ops1} if ops is None else ops, host=[],
                 window_s=1e-5)
    return harness.ReadContext(trace=tr, info={"steps": steps, "chips": 2},
                               cell=cell or FakeCell(), peaks=None)


def test_scopes_of_takes_off_autodiff_wrappers():
    assert scopes.scopes_of("jit(step)/transpose(jvp(head))/dot_general") \
        == {"step", "head", "dot_general"}
    assert "wkv" in scopes.scopes_of(NAMES["fusion.2"])
    assert "wkv" not in scopes.scopes_of(NAMES["fusion.3"])
    assert "pgtune.matmul_accumulate.default" in scopes.scopes_of(
        NAMES["fusion.3"])


@pytest.mark.parametrize("metric,ns", [
    # mean over the two chips, over two steps, in ms
    ("wkv_ms.train", (100 + 300 + 200 + 100) / 2),
    ("head_ms.train", (40 + 60) / 2),
    ("optimizer_ms.train", (80 + 100) / 2),
])
def test_scope_readers_by_hand(metric, ns):
    got = harness.load_reader(metric)(_ctx(FakeCell()))
    assert got == pytest.approx(ns / 2 * 1e-6)


def _module(names):
    """A compiled module's text: computations whose instructions are in
    schedule order, each given as ``(name, op_name or None)``."""
    lines = []
    for comp in names:
        lines.append("%comp (p: f32[8]) -> f32[8] {")
        for n, op in comp:
            meta = f', metadata={{op_name="{op}"}}' if op else ""
            lines.append(f"  %{n} = f32[8]{{0}} add(%p, %p){meta}")
        lines.append("}")
    return "\n".join(lines) + "\n"


def test_names_are_carried_by_place_to_the_step_that_ran():
    # the executable that ran came from another checkout: the same
    # program, numbered otherwise, with no scopes in its names
    ran = _module([[("fusion.3", "jit(step)/dot_general"),
                    ("reshape.1040", None)],
                   [("reshape.1047", "jit(step)/gather")]])
    own = _module([[("fusion.3", "jit(step)/wkv/dot_general"),
                    ("reshape.1052", None)],
                   [("reshape.1059", "jit(step)/jvp(embed)/gather")]])
    assert scopes.carry_names(ran, own) == {
        "fusion.3": "jit(step)/wkv/dot_general",
        "reshape.1047": "jit(step)/jvp(embed)/gather"}
    # modules that do not line up: this checkout's own names
    other = _module([[("fusion.3", "jit(step)/dot_general")]])
    assert scopes.carry_names(other, own) == T.hlo_op_names(own)


def test_block_scope_is_not_the_wkv_scope():
    # rwkv alone (fusion.3, 50 ns on chip 0) is the block's, not the scan's
    assert scopes.scope_ms(_ctx(), "rwkv") == pytest.approx(
        (100 + 300 + 50 + 200 + 100) / 2 / 2 * 1e-6)


def test_scope_reader_reads_nothing_without_the_scope():
    # the parent commit: the same step with no scopes in its name stacks
    bare = {n: "jit(step)/jit(main)/" + s.rsplit("/", 1)[-1]
            for n, s in NAMES.items()}
    for metric in ("wkv_ms.train", "head_ms.train", "optimizer_ms.train"):
        assert harness.load_reader(metric)(_ctx(FakeCell(bare))) is None


def test_scope_readers_read_nothing_without_device_ops_or_steps():
    for metric in ("wkv_ms.train", "head_ms.train", "optimizer_ms.train"):
        read = harness.load_reader(metric)
        assert read(_ctx(ops={})) is None
        assert read(_ctx(steps=0)) is None


def test_recompiles_reader():
    read = harness.load_reader("recompiles.train")
    assert read(_ctx(FakeCell(recompiles=0))) == 0
    assert read(_ctx(FakeCell(recompiles=2))) == 2
    # a trainer that does not count (the parent commit), or none at all
    cell = FakeCell()
    cell.trainer = types.SimpleNamespace()
    assert read(_ctx(cell)) is None
    assert read(harness.ReadContext(trace=T.Trace({}, [], 1.0), info={},
                                    cell=None, peaks=None)) is None


def test_op_names_of_a_small_train_cell():
    """The real path on the CPU: the step compiled twice (as it ran, and
    afresh with the persistent cache off), its scopes found, the cache's
    setting left as it was."""
    import jax
    from bench.kinds import train
    from bench.tests.rehearse import TINY_TRAIN
    spec = harness.cell_spec(harness.load_json(harness.ROOT
                                               / "BENCHMARK.json"),
                             "rwkv6-static-mix-train-1x1")
    config = dict(spec.config, **TINY_TRAIN, overrides=sorted(
        set(spec.config["overrides"]) | set(TINY_TRAIN)))
    cell = train.Cell(config, dict(spec.traffic, seq=64), spec.limits,
                      seed=2**31 + 7, devices=jax.devices()[:1])
    cell.setup()
    was = jax.config.jax_enable_compilation_cache
    names = scopes.op_names(cell)
    assert jax.config.jax_enable_compilation_cache == was
    assert scopes.op_names(cell) is names
    found = set().union(*map(scopes.scopes_of, names.values()))
    assert {"wkv", "head", "optimizer", "embed", "rwkv"} <= found
    cell.op_label("")
    assert set(names) == set(cell.hlo_names)
