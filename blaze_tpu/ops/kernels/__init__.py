"""Pallas TPU kernels for hot ops the XLA fusion path doesn't already own.

SURVEY 7 design stance: "hash partition = murmur3 (bit-exact Spark
semantics) as a Pallas kernel". Everything here has an interpret-mode
test path (tests/test_pallas_kernels.py) so the CPU test mesh exercises
the same code. What the TPU v5e compiler says of each, compiled for a
described chip in tests/test_chip_compile.py:

  murmur3_pallas    accepted; the only kernel on a default path
                    (ops/shuffle_writer.py, backend "tpu"); RAN on a
                    v5e in chip_smoke.py's repartition sends
  stats_pallas      accepted; no call site; never ran on a chip
  segreduce_pallas  REFUSED (output block shape, then an in-kernel
                    relayout); no call site left; never ran on a chip
  compact_pallas    REFUSED (SMEM count block shape); no call site;
                    never ran on a chip
"""
