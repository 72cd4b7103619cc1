"""The simulator's programs compile for a TPU v5e, with no chip attached.

The TPU compiler is installed wherever JAX is; it compiles for a
*described* topology (``topologies.get_topology_desc``) and raises what
the chip's compiler would raise — an unsupported op, a layout the
lowering refuses, a program that does not fit the device — at no chip
time.  Nothing runs, so these say nothing about results or speed.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and under several test
workers only the worker given this file must try.  The persistent
compilation cache is off around these compiles: an executable compiled
for a described chip is written to it but cannot be read back here.
"""
import os
import re
from functools import partial

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import distribute
from repro.core.batch import stack_workloads
from repro.core.engine import PHASES, build_simulation
from repro.core.parallel import make_sm_runner
from repro.core.plan import RunPlan
from repro.core.sweep import batched_init, make_pair_runner, stack_dyn
from repro.launch.dse import default_grid
from repro.sim.config import RTX3080TI, split_config
from repro.sim.memsys import mem_phase
from repro.sim.smcore import sm_cycle_single
from repro.sim.state import init_state
from repro.sim.workloads import zoo_workload
from repro.workloads import make_workload


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def shapes_of(tree, sharding):
    """ShapeDtypeStructs of a pytree's leaves; ``sharding`` is one
    sharding for every leaf or a matching pytree of them."""
    if not isinstance(sharding, dict):
        sharding = jax.tree_util.tree_map(lambda _: sharding, tree)
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=s),
        tree, sharding)


def test_simulate_80sm_compiles_for_v5e(one_chip):
    """The long-run program (hotspot at scale 1.0 on the 80-SM model, the
    path of repro.launch.simulate) compiles for one v5e chip and fits, and
    every phase scope survives the TPU compiler in its ops' metadata."""
    w = make_workload("hotspot", scale=1.0)
    run, scfg, dyn = build_simulation(
        w, RTX3080TI, make_sm_runner(RTX3080TI, "vmap"),
        RunPlan(max_cycles=1 << 17))
    state = jax.eval_shape(partial(init_state, scfg))
    compiled = run.lower(shapes_of(state, one_chip),
                         shapes_of(dyn, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    assert set(re.findall(r"(?<![\w.])sim\.\w+", compiled.as_text())) == \
        set(PHASES)


# an HLO computation's header line, and an instruction's opcode: the first
# lower-case word followed by "(" after the "=" (shapes and layouts hold
# no such word)
HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s.*\{\s*$")
HLO_OPCODE = re.compile(r"^\s+(?:ROOT\s+)?%[^\s=]+\s*=\s*.*?\s([a-z][\w-]*)\(")
HLO_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply)=%([^\s,]+)")
HLO_BODY = re.compile(r"\bbody=%([^\s,]+)")


def hlo_computations(text):
    """{computation name: [its instruction lines]} of HLO text."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = HLO_COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and HLO_OPCODE.match(line):
            cur.append(line)
    return comps


def sm_cycle_body(comps):
    """The SM-cycle loop: the innermost ``while`` body whose instructions
    carry the ``sim.sm_phase`` scope."""
    bodies = {HLO_BODY.search(line).group(1)
              for lines in comps.values() for line in lines
              if HLO_OPCODE.match(line).group(1) == "while"}
    sm = {b for b in bodies if any("sim.sm_phase" in line
                                   for line in comps[b])}
    inner = [b for b in sm if not any(
        HLO_OPCODE.match(line).group(1) == "while"
        and HLO_BODY.search(line).group(1) in sm for line in comps[b])]
    assert len(inner) == 1, inner
    return comps[inner[0]]


def uses(comps, line, opcode, seen=None):
    """Whether an instruction is ``opcode`` or calls a computation that
    holds one (a fusion holding a scatter is a scatter kernel)."""
    seen = set() if seen is None else seen
    if HLO_OPCODE.match(line).group(1) == opcode:
        return True
    for c in HLO_CALLS.findall(line):
        if c not in seen:
            seen.add(c)
            if any(uses(comps, inner, opcode, seen) for inner in comps[c]):
                return True
    return False


def test_sm_cycle_compiles_without_scatters_for_v5e(one_chip):
    """The SM cycle addresses SM-local state by one-hot masks, so the
    SM-cycle loop of the 80-SM program, compiled for one v5e, holds no
    scatter kernel, and gathers only for the instruction fetch."""
    w = make_workload("lavaMD", scale=0.25)
    run, scfg, dyn = build_simulation(
        w, RTX3080TI, make_sm_runner(RTX3080TI, "vmap"),
        RunPlan(max_cycles=1 << 17))
    state = jax.eval_shape(partial(init_state, scfg))
    comps = hlo_computations(run.lower(shapes_of(state, one_chip),
                                       shapes_of(dyn, one_chip))
                             .compile().as_text())
    body = sm_cycle_body(comps)
    scatters = [line for line in body if uses(comps, line, "scatter")]
    gathers = [line for line in body if uses(comps, line, "gather")]
    assert not scatters, scatters[:3]
    assert len(gathers) <= 8, len(gathers)


# instructions that move no data and run no kernel, left out of op counts
TRIVIAL = {"get-tuple-element", "bitcast", "constant", "parameter", "tuple"}
HLO_NAME_TYPE = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*?)\s"
                           r"[a-z][\w-]*\(")
HLO_OPERANDS = re.compile(r"\s[a-z][\w-]*\(([^)]*)\)")


def executed(comps, text):
    """The computations a program runs op by op: its entry and the
    bodies and conditions of the ``while`` loops those hold (a fusion's
    computation is one kernel of its caller, and not among them)."""
    todo = [re.search(r"^ENTRY\s+%(\S+)", text, re.M).group(1)]
    done = []
    while todo:
        name = todo.pop()
        done.append(name)
        for line in comps[name]:
            if HLO_OPCODE.match(line).group(1) == "while":
                todo += [c for c in re.findall(
                    r"\b(?:body|condition)=%([^\s,]+)", line)
                    if c not in done + todo]
    return done


def index_ops_over(comps, rows):
    """Sort, gather and scatter instructions, inside fusions too, with a
    ``rows``-long dimension in their result or in an operand."""
    found = []
    for lines in comps.values():
        types = {HLO_NAME_TYPE.match(x).group(1): HLO_NAME_TYPE.match(x)
                 .group(2) for x in lines}
        for line in lines:
            if HLO_OPCODE.match(line).group(1) not in ("sort", "gather",
                                                        "scatter"):
                continue
            args = re.findall(r"%([^\s,]+)",
                              HLO_OPERANDS.search(line).group(1))
            shapes = [HLO_NAME_TYPE.match(line).group(2)] + \
                [types.get(a, "") for a in args]
            if any(str(rows) in re.findall(r"\d+", ",".join(
                    re.findall(r"\[([\d,]*)\]", sh))) for sh in shapes):
                found.append(line.strip()[:160])
    return found


def test_mem_phase_compiles_without_whole_table_index_ops(one_chip):
    """The memory phase serves the due requests in 128-row chunks through
    one-hot masks, so ``mem_phase`` alone at the 80-SM shapes (80 x 32 =
    2,560 request rows), compiled for one v5e, holds no sort, gather or
    scatter over the request table, and at most 150 non-trivial ops in
    the computations it runs op by op (the sorted and scanned
    formulation before it: 497, 4 sorts, 17 gathers and 11 scatters of
    2,560 rows)."""
    scfg, dyn = split_config(RTX3080TI)
    rows = scfg.n_sm * scfg.mshr_per_sm
    assert rows == 2560
    state = jax.eval_shape(partial(init_state, scfg))
    phase = jax.jit(lambda req, mem, stats, t0, dyn, sm_ids: mem_phase(
        req, mem, stats, t0, scfg, dyn, sm_ids=sm_ids))
    args = (state["req"], state["mem"], state["stats"],
            jax.ShapeDtypeStruct((), jnp.int32), dyn,
            state["ctrl"]["sm_ids"])
    text = phase.lower(*shapes_of(args, one_chip)).compile().as_text()
    comps = hlo_computations(text)
    ops = [line for c in executed(comps, text) for line in comps[c]
           if HLO_OPCODE.match(line).group(1) not in TRIVIAL]
    assert any(HLO_OPCODE.match(line).group(1) == "while" for line in ops)
    assert index_ops_over(comps, rows) == []
    assert len(ops) <= 150, len(ops)


def test_sm_cycle_jaxpr_gathers_only_from_the_trace():
    """The CPU twin of the test above, on the jaxpr of the SM cycle
    vmapped over the 80 SMs: no scatter of any kind, and one gather, the
    instruction fetch, from a table computed from the trace's
    instruction tables and constants alone — no SM state."""
    scfg, dyn = split_config(RTX3080TI)
    state = jax.eval_shape(partial(init_state, scfg))
    trace = make_workload("lavaMD", scale=0.25).kernels[0].pack()
    names = sorted(trace)

    def cycle(trace, warp, sm, req, stats):
        return jax.vmap(lambda w, s, r, st: sm_cycle_single(
            w, s, r, st, trace, jnp.int32(7), scfg, dyn))(
            warp, sm, req, stats)

    closed = jax.make_jaxpr(cycle)(trace, state["warp"], state["sm"],
                                   state["req"], state["stats_sm"])
    tables = [v for v, n in zip(closed.jaxpr.invars, names)
              if n in ("ops", "dep", "addr_mode", "addr_param")]
    gathers = []

    def walk(jaxpr, tables):
        """``tables``: the variables of ``jaxpr`` computed from the trace
        tables and constants alone, grown equation by equation (by
        identity: literals are not hashable)."""
        def table(x):
            return any(x is v for v in tables)

        for e in jaxpr.eqns:
            name = e.primitive.name
            assert not name.startswith("scatter"), name
            if name == "gather":
                assert table(e.invars[0]), e
                gathers.append(e)
            for p in e.params.values():
                sub = getattr(p, "jaxpr", p)
                if hasattr(sub, "eqns"):
                    walk(sub, [i for i, o in zip(sub.invars, e.invars)
                               if table(o)])
            if all(table(x) for x in e.invars
                   if isinstance(x, jex_core.Var)):
                tables = tables + list(e.outvars)

    walk(closed.jaxpr, tables)
    assert len(gathers) == 1, len(gathers)


def test_pair_runner_compiles_for_v5e(one_chip):
    """The server's pair-lane program (core/sweep.py:make_pair_runner) at
    RTX3080TI width over a few heterogeneous lanes."""
    workloads = [zoo_workload("mixed", scale=0.25),
                 zoo_workload("trace:vecadd"),
                 zoo_workload("mixed", scale=0.25)]
    cfgs = default_grid(RTX3080TI, 3)
    scfg, dyn_b = stack_dyn(cfgs)
    runner = make_pair_runner(scfg, "vmap", 1 << 15)
    state = jax.eval_shape(partial(batched_init, scfg, len(cfgs)))
    runner.lower(shapes_of(state, one_chip),
                 shapes_of(stack_workloads(workloads), one_chip),
                 shapes_of(dyn_b, one_chip)).compile()


def test_mesh_grid_2x2_compiles_for_v5e(topo):
    """The 2-D ('cfg','sm') grid program (core/distribute.py) over the
    four chips of a v5e 2x2: lanes over 'cfg', each lane's SMs over 'sm'.
    The compiled program holds the 'sm' all-gathers."""
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2),
                (distribute.CFG_AXIS, distribute.SM_AXIS))
    workloads = [zoo_workload("mixed", scale=0.25),
                 zoo_workload("trace:vecadd")]
    cfgs = default_grid(RTX3080TI, 4)
    scfg, dyn_b = stack_dyn(cfgs)
    runner = distribute.make_dist_grid_runner(scfg, mesh, 1 << 15)
    state = jax.eval_shape(partial(batched_init, scfg, len(workloads),
                                   len(cfgs)))
    specs = distribute.state_specs(None, distribute.CFG_AXIS)
    state_sh = {k: jax.tree_util.tree_map(
                    lambda _, s=specs[k]: NamedSharding(mesh, s), v)
                for k, v in state.items()}
    compiled = runner.lower(
        shapes_of(state, state_sh),
        shapes_of(stack_workloads(workloads), NamedSharding(mesh, P())),
        shapes_of(dyn_b, NamedSharding(mesh, P(distribute.CFG_AXIS))),
    ).compile()
    assert "all-gather" in compiled.as_text()


def test_sm_issue_kernel_is_refused_by_the_tpu_lowering(one_chip):
    """The Pallas issue kernel (kernels/sm_issue) at real widths — 80 SMs
    x 48 warps, 4 sub-cores — with interpret=False.  As written it cannot
    be wired in: its (1, 48) per-SM block is refused by the TPU lowering,
    which wants the last two block dims divisible by (8, 128) or equal to
    the array's.  A rewrite that compiles turns this into a plain
    compile."""
    from repro.kernels.sm_issue.kernel import issue_select_pallas

    n_sm, warps, sub, n_instr = 80, 48, 4, 64
    i32, b = jnp.int32, jnp.bool_
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((n_sm, warps), i32), ((n_sm, warps), b), ((n_sm, warps), i32),
        ((n_sm, warps), i32), ((n_sm, warps), b), ((n_sm, sub), i32),
        ((n_sm, sub, 5), i32), ((n_instr,), i32), ((n_instr,), b),
        ((), i32))]
    kernel = jax.jit(partial(issue_select_pallas, n_subcores=sub,
                             interpret=False))
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        kernel.lower(*args).compile()
