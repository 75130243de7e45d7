"""A production simulation workflow on TPU-class backends.

The habits that matter when dispatch latency and compile time are real
costs (measured numbers in docs/tpu.md):

1. persistent compilation cache — re-runs skip every warm compile;
2. ahead-of-time compilation (`precompile`) — no hidden compile inside
   the first timed/production call;
3. one-pass multi-shot sampling (`sampleOutcomes`) — M shots without M
   register copies, shard-local on a mesh;
4. precision control — compensated f32 scalars by default, double-double
   registers when a result must be f64-class on f32 hardware.

Runs unchanged on CPU (seconds) and on a TPU chip. Run:
    python examples/production_workflow.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))   # run from anywhere, uninstalled

import numpy as np

import quest_tpu as qt
from quest_tpu import compile_cache
from quest_tpu.circuits import Circuit

# 1. persistent compilation cache --------------------------------------------
# every compile slower than a second is saved to disk; identical programs
# (same circuit, shapes, mesh) load in milliseconds on any later run.
# JAX_COMPILATION_CACHE_DIR places it; otherwise it is <repo>/.jax_cache
compile_cache.enable()

env = qt.createQuESTEnv(num_devices=1, seed=[11])
n = 16

# a parameterized ansatz: one executable serves every angle
c = Circuit(n)
theta = c.parameter("theta")
for i in range(n):
    c.h(i)
for i in range(n - 1):
    c.cnot(i, i + 1)
c.rz(n // 2, theta)
for i in range(n):
    c.rx(i, 0.1 + 0.05 * i)

# 2. compile ahead of time ----------------------------------------------------
t0 = time.perf_counter()
cc = c.compile(env).precompile()
print(f"compiled AOT in {time.perf_counter() - t0:.2f}s "
      f"(cached for every later run of this script)")

q = qt.createQureg(n, env)
qt.initZeroState(q)
t0 = time.perf_counter()
cc.run(q, params={"theta": 0.37})       # pure dispatch — nothing compiles here
q.state.block_until_ready()
print(f"first production dispatch: {1e3 * (time.perf_counter() - t0):.1f} ms")

# 3. multi-shot sampling in one pass ------------------------------------------
shots = qt.sampleOutcomes(q, 4096)       # state untouched, env RNG advances
counts = np.bincount(shots & 0b111, minlength=8)
print("low-3-qubit histogram over 4096 shots:", counts.tolist())
assert abs(float(qt.calcTotalProb(q)) - 1.0) < 1e-6

# 4. precision tiers ----------------------------------------------------------
# f32 registers + compensated reductions give f64-class scalar results on
# f32 hardware; QUAD double-double registers when amplitudes themselves
# must carry ~f64 precision (see examples/quad_precision.py)
p = float(qt.calcProbOfOutcome(q, 0, 0))
print(f"calcProbOfOutcome(q0=0) = {p:.9f} (compensated reduction)")

print("workflow complete")
