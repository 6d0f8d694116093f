"""The grid of ``grid.py`` through ``whatif.sharded_replay_grid`` on a
fleet mesh of the cell's chips, streamed in blocks of
``block_scenarios``."""
from __future__ import annotations

from bench.drivers.grid import Driver as GridDriver


class Driver(GridDriver):

    def __init__(self, cell, seed: int):
        super().__init__(cell, seed)
        from repro.core.whatif import sharded_replay_grid
        from repro.launch.mesh import make_fleet_mesh
        block = int(self.traffic["block_scenarios"])
        run = sharded_replay_grid(make_fleet_mesh(cell.chips),
                                  engine=self.engine, objective=self.goal,
                                  block_size=block)
        self.pass_k = block * len(self.pool) // cell.chips
        self._call = lambda: run(self.scenarios, self.pool.spec)
