"""run_grid: one server's compiled simulator over many lanes.

One call samples every lane's arrivals from the seed, pads them
(``pad_arrivals_batch``) and runs the grid kernel with the deployment's
table (bench/entry.py ``Simulation``).
"""
from __future__ import annotations

from entry import Simulation


class RunGrid(Simulation):
    kind = "single"
    KERNEL = "_grid_jit"

    def __init__(self, cfg, mix, seed, test=False):
        super().__init__(cfg, mix, seed, test)
        from repro.serving import run_grid

        self.program["run_grid"] = run_grid
        # the epoch budget is fixed by the slot count, never by the draw
        self.max_epochs = 2 * self.slots + 2

    def dispatch(self, arr):
        return self.program["run_grid"](
            self.table[None], arr, means=self.lat, zeta=self.zeta,
            b_max=self.cfg["b_max"], max_epochs=self.max_epochs,
            **self.options)


ENTRY = RunGrid
