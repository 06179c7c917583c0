"""Closed loop of ``solve(spec)`` on the sharded log-domain front door.

The same problems as ``drivers/solve.py``, with the points' rows split over
a mesh of all the cell's chips (axis ``data``) and the anchors replicated;
every spec runs ``method="sharded_log"`` under ``ExecutionPolicy(mesh=...)``.
"""
from __future__ import annotations

import numpy as np

from bench.drivers import solve as _solve


class Driver(_solve.Driver):
    def placement(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        self.mesh = Mesh(np.array(self.devices), ("data",))
        return NamedSharding(self.mesh, P("data", None)), \
            NamedSharding(self.mesh, P())

    def policy(self):
        from repro.core.objective import ExecutionPolicy
        return ExecutionPolicy(mesh=self.mesh)
