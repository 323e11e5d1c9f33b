"""perfbench: mxnet_tpu's benchmark (see perfbench/README.md).

Everything that decides a number lives here, where a PR that claims a
gain cannot change it: traffic generation, FLOP and byte counts, the
peak table, the trace reduction, the plain references and the
comparison that decides ``correct``.  Only ``perfbench/adapters/``
imports ``mxnet_tpu``.
"""
