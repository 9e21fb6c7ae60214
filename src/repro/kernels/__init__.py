"""Pallas TPU kernels for the paper's compute hot spots.

  lstm_scan / gru_scan — the paper's STATIC MODE on TPU: one weights-resident
      block (VMEM ~ BRAM) scans the sequence, state lives in VMEM scratch.
  hadamard             — the elementwise product the paper added to hls4ml.
  fixed_point          — ap_fixed<W,I> quantization on-chip.
  rglru_scan           — the RG-LRU gated linear recurrence (recurrentgemma).
  reuse_matmul         — reuse-factor analogue: K-serialized matmul whose
      VMEM working set shrinks by R while latency grows by R.
  col_matmul           — column-serialized matmul: the non-static per-
      timestep block with the gate matmul split into R sequential tiles.

Every scan kernel dispatches through the reuse-factor scheduling layer
(schedule.KernelSchedule via ops.py): reuse_factor partitions gate matmuls
into sequential column tiles, mode selects static (one weights-resident
block) vs non-static (one block per timestep), and the same schedule object
feeds core.hls's latency/DSP estimators.

Kernels target TPU (Mosaic).  ``backend="auto"`` compiles them on a TPU and
runs them in the Pallas interpreter on the CPU, where the tests check them
against the pure-jnp oracles in ref.py.  The XLA model paths are used for
dry-run lowering (DESIGN.md Sec. 3).
"""
