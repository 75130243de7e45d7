"""Trace-level safety net for the TPU bench shapes (VERDICT r2 Weak #3).

The compiled Pallas fused-layer path can only EXECUTE on a real chip (or
under slow interpret mode at small sizes, ``tests/test_pallas_layers.py``),
but its grid construction, block index maps, and layer-collection logic all
run at trace time — so ``jax.eval_shape`` over the exact register sizes the
bench uses catches the Python- and abstract-shape-level failure modes
without compiling a kernel. interpret=True follows the identical collection
+ pallas_call construction code path as the real-TPU pallas="on".
"""

import jax
import numpy as np
import pytest

import quest_tpu as qt
from bench import build_bench_circuit


def _trace(circ, n, env):
    cc = circ.compile(env, pallas="interpret")
    n_layers = sum(1 for op in cc._ops if op.kind == "layer")
    state = jax.ShapeDtypeStruct((2, 1 << n), np.float32)
    params = jax.ShapeDtypeStruct((0,), np.float32)
    out = jax.eval_shape(cc._apply_fn, state, params)
    assert out.shape == (2, 1 << n) and out.dtype == np.float32
    return n_layers


@pytest.fixture
def f32_env():
    return qt.createQuESTEnv(num_devices=1, seed=[1],
                             precision=qt.SINGLE)


@pytest.mark.parametrize("n", [22, 26])
def test_bench_brickwork_traces_with_layers(n, f32_env):
    circ, _ = build_bench_circuit(n, 1)
    n_layers = _trace(circ, n, f32_env)
    assert n_layers >= 1, "layer collector produced no Pallas layers"


def test_bench_qft_grover_trace(f32_env):
    from quest_tpu.algorithms import qft, grover
    assert _trace(qft(24), 24, f32_env) >= 1
    assert _trace(grover(24, marked=5, num_iterations=4), 24, f32_env) >= 1


class TestShardedVmemBudget:
    """The Mosaic scoped-VMEM estimator against the EXACT per-chip stage
    chains ``_collect_layers_plan`` emits for the bench workloads under
    ``shard_bits in {1, 2, 3}``: after block-row shrinking
    (``choose_block_rows``) every sharded chain must fit the 16 MiB
    default budget — the limit the estimate of the UNSHARDED 22q
    brickwork layer exceeds (ops/pallas_kernels.py VMEM notes)."""

    OOM_BUDGET = 16 * 1024 * 1024     # the default Mosaic vmem limit
    F32 = 4                           # bench planes are float32

    @staticmethod
    def _per_chip_layers(circ, num_qubits, shard_bits):
        """The layer set the compiled shard_map local body would run:
        fuse -> plan -> post-plan layer peephole at per-chip width."""
        from quest_tpu.circuits import _collect_layers_plan
        from quest_tpu.core.fusion import fuse_ops
        from quest_tpu.parallel import plan_layout
        ops, _ = fuse_ops(list(circ.ops), max_k=3, diag_row_cap=3)
        plan = plan_layout(ops, num_qubits, shard_bits)
        items, table = _collect_layers_plan(plan.items, ops,
                                            num_qubits - shard_bits)
        return [table[it[1]] for it in items
                if it[0] == "op" and getattr(table[it[1]], "kind",
                                             None) == "layer"]

    @classmethod
    def _plan_and_estimate(cls, layer, num_local, budget=None):
        from quest_tpu.ops import pallas_kernels as pk
        kstages, mats, tables, xmats, block_rows, _ = \
            pk.layer_kernel_plan(layer, num_local)
        mstack = (np.stack(mats) if mats
                  else np.zeros((1, 128, 128), np.complex128))
        tstack = (np.stack(tables) if tables
                  else np.zeros((1, 128), np.complex128))
        xstack = (np.stack(xmats) if xmats
                  else np.zeros((1, 8, 8), np.complex128))
        return pk.choose_block_rows(kstages, mstack, tstack, block_rows,
                                    cls.F32, budget or cls.OOM_BUDGET,
                                    xstack)

    def test_unsharded_22q_layer_exceeds_default_budget(self):
        """Documents the failure mode the estimator exists for: at least
        one 22q brickwork chain overflows 16 MiB at the default block
        size (pre-shrink), as measured on silicon."""
        from quest_tpu.ops import pallas_kernels as pk
        circ, _ = build_bench_circuit(22, 1)
        layers = self._per_chip_layers(circ, 22, 0)
        assert layers
        raw = []
        for layer in layers:
            kstages, mats, tables, _xmats, block_rows, _ = \
                pk.layer_kernel_plan(layer, 22)
            mstack = (np.stack(mats) if mats
                      else np.zeros((1, 128, 128), np.complex128))
            tstack = (np.stack(tables) if tables
                      else np.zeros((1, 128), np.complex128))
            raw.append(pk._vmem_estimate(block_rows, kstages, mstack,
                                         tstack, self.F32))
        assert max(raw) > self.OOM_BUDGET, raw

    @pytest.mark.parametrize("shard_bits", [1, 2, 3])
    def test_bench_brickwork_chains_fit_per_chip(self, shard_bits):
        circ, _ = build_bench_circuit(22, 1)
        layers = self._per_chip_layers(circ, 22, shard_bits)
        assert layers, "collector produced no per-chip layers"
        for layer in layers:
            block_rows, est = self._plan_and_estimate(
                layer, 22 - shard_bits)
            assert est <= self.OOM_BUDGET, (shard_bits, block_rows, est)
            # shrinking must keep the grid well-formed
            total_rows = (1 << (22 - shard_bits)) // 128
            assert total_rows % block_rows == 0

    @pytest.mark.parametrize("shard_bits", [1, 2, 3])
    def test_qft22_chains_fit_operative_budget(self, shard_bits):
        """QFT's per-chip chains include row gates at the top of the mid
        range (stride = block/2), which pin the pairing floor at the full
        default block — the shrink loop cannot go below it, so these
        chains are exactly why apply_layer RAISES the limit toward the
        chip's real VMEM (QUEST_PALLAS_VMEM_LIMIT, default 100 MB)
        instead of only shrinking. Assert they fit the operative budget
        and that the floor is respected (no malformed grid)."""
        from quest_tpu.algorithms import qft
        operative = 100 * 1024 * 1024
        layers = self._per_chip_layers(qft(22), 22, shard_bits)
        assert layers
        for layer in layers:
            block_rows, est = self._plan_and_estimate(
                layer, 22 - shard_bits, budget=operative)
            assert est <= operative, (shard_bits, block_rows, est)
            total_rows = (1 << (22 - shard_bits)) // 128
            assert total_rows % block_rows == 0
            assert block_rows >= 8
