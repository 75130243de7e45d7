"""The closed loop of ``circuit_loop`` on a register sharded over the
cell's chips.

Set-up, window and check are those of ``circuit_loop``. The plan's
readings add what the program's exchange issues per circuit, from
``CompiledCircuit.dispatch_stats()``: ``collective_launches``,
``cross_shard_exchanges`` and ``comm_bytes_planned`` (mesh-total bytes).
The readings also hand the metrics ``circuits``, the runs the window
completed.
"""

from __future__ import annotations

from . import circuit_loop


class Driver(circuit_loop.Driver):
    def setup(self) -> None:
        super().setup()
        stats = self.cc.dispatch_stats()
        self.stats.update(
            collective_launches=int(stats.collective_launches),
            cross_shard_exchanges=int(stats.cross_shard_exchanges),
            comm_bytes_planned=float(stats.comm_bytes_planned))

    def readings(self) -> dict:
        return {**super().readings(), "circuits": self.attempted}
