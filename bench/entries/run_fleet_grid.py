"""run_fleet_grid: the routed fleet simulator over lanes and routers.

One call samples every lane's arrivals from the seed, pads them and runs
the fleet kernel with the deployment's table on every replica, once per
configured router (bench/entry.py ``Simulation``).  The option ``"mesh":
"lanes"`` shards the lanes over every chip the run sees.
"""
from __future__ import annotations

from entry import Simulation


class RunFleetGrid(Simulation):
    kind = "fleet"
    KERNEL = "_fleet_grid_core"
    # sharding the lanes leaves every lane's answer as it is
    OPTIONS = {"mesh": ("lanes",)}

    def __init__(self, cfg, mix, seed, test=False):
        super().__init__(cfg, mix, seed, test)
        from repro.serving import run_fleet_grid

        self.program["run_fleet_grid"] = run_fleet_grid
        self.max_epochs = 2 * self.slots + cfg["replicas"] + 4

    def program_options(self, options):
        out = dict(options)
        if out.get("mesh") == "lanes":
            from repro.launch.mesh import make_sim_mesh

            out["mesh"] = make_sim_mesh()
        return out

    def routers(self):
        return list(self.cfg["routers"])

    def dispatch(self, arr):
        return self.program["run_fleet_grid"](
            self.table[None], arr, routers=tuple(self.cfg["routers"]),
            n_replicas=self.cfg["replicas"], means=self.lat, zeta=self.zeta,
            b_max=self.cfg["b_max"], max_epochs=self.max_epochs,
            **self.options)


ENTRY = RunFleetGrid
