"""Runs one cell of the benchmark on the CPU backend, for the tests only:
the one thing it changes is the refusal to run without a TPU. What it
prints is a rehearsal of the control flow, never a measurement.

    python cpu_rehearsal.py <devices> --workload ... (run.py's arguments)
"""
import sys

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", int(sys.argv[1]))

from perfbench import harness, run  # noqa: E402

harness.require_tpu = lambda chips: jax.devices()[:chips]
sys.exit(run.main(sys.argv[2:]))
