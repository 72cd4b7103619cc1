"""One simulation at a time on one chip: the whole workload as the one
program ``engine.build_simulation`` builds (SM phase vmapped over the
SMs), called back to back, at the configuration's own timing point. The
seed draws the SM labels of the initial state (``program.sm_labels``)."""
from __future__ import annotations

import jax

import program
from cells import default_point, gpu_config, workload


class Runner:
    batched = False

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices: list):
        from repro.core.engine import build_simulation
        from repro.core.parallel import make_sm_runner
        from repro.sim.config import static_part
        from repro.sim.state import init_state

        self.points = [default_point(cfg)]
        gcfg = gpu_config(cfg, self.points[0])
        scfg = static_part(gcfg)
        self.quantum = scfg.quantum
        self.devices = devices[:1]
        run, _, dyn = build_simulation(workload(cfg), gcfg,
                                       make_sm_runner(scfg, "vmap"))
        self._run, self._dyn = run, jax.device_put(dyn, self.devices[0])
        self._labels = program.sm_labels(scfg.n_sm, seed)
        self._init = jax.jit(
            lambda ids: program.relabel(init_state(scfg), ids))

    def compile(self):
        self._init = self._init.lower(self._labels).compile()
        self._run = self._run.lower(self.init(), *self.args()).compile()

    def init(self):
        return self._init(self._labels)

    def args(self) -> tuple:
        """The program's arguments after the initial state."""
        return (self._dyn,)

    def launch(self, state):
        return self._run(state, *self.args())

    def sample(self, rng, stats: list) -> list:
        return [0]


def build(cfg, traffic, seed, devices):
    return Runner(cfg, traffic, seed, devices)
