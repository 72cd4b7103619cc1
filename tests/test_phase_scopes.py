"""The quantum loop's phases are named scopes in every compiled execution
mode: each name of ``engine.PHASES`` is the innermost ``sim.*`` segment of
the ``op_name`` of at least one instruction of the compiled TINY solo
program, in the vmap, seq and shard modes and on a two-device
('cfg', 'sm') mesh (subprocess: jax fixes the host device count at its
first start)."""
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

from repro.core.engine import PHASES, build_simulation
from repro.core.parallel import make_sm_runner
from repro.launch.mesh import make_host_mesh
from repro.sim.config import TINY, split_config
from repro.sim.state import init_state
from repro.sim.workloads import zoo_workload

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OP_NAME = re.compile(r'op_name="([^"]*)"')


def phases_named(hlo_text: str) -> set:
    """The innermost sim.* segment of every op_name in a program's text."""
    out = set()
    for op in OP_NAME.findall(hlo_text):
        scopes = re.findall(r"(?<![\w.])sim\.\w+", op)
        if scopes:
            out.add(scopes[-1])
    return out


@pytest.mark.parametrize("mode", ["vmap", "seq", "shard"])
def test_every_phase_tags_the_solo_program(mode):
    scfg, _ = split_config(TINY)
    mesh = make_host_mesh(1, "sm") if mode == "shard" else None
    run, _, dyn = build_simulation(zoo_workload("mixed", scale=0.02), TINY,
                                   make_sm_runner(scfg, mode, mesh))
    text = run.lower(init_state(scfg), dyn).compile().as_text()
    assert phases_named(text) == set(PHASES)


MESH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import json
    from repro.core.batch import stack_kernels
    from repro.core.distribute import (make_dist_sweep_runner, make_mesh,
                                       place_lanes, place_state)
    from repro.core.sweep import batched_init, stack_dyn
    from repro.sim.config import TINY
    from repro.sim.workloads import zoo_workload

    w = zoo_workload("mixed", scale=0.02)
    scfg, dyn = stack_dyn([TINY])
    mesh = make_mesh(1, 2)
    runner = make_dist_sweep_runner(scfg, mesh, max_cycles=1 << 14)
    state = place_state(batched_init(scfg, 1), mesh, "cfg")
    text = runner.lower(state, stack_kernels([k.pack() for k in w.kernels]),
                        place_lanes(dyn, mesh)).compile().as_text()
    print(json.dumps(text))
""")


def test_every_phase_tags_the_two_device_mesh_program():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", MESH], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    text = json.loads(out.stdout.strip().splitlines()[-1])
    assert "all-gather" in text
    assert phases_named(text) == set(PHASES)
