"""The system under test, built from a configuration file.  These are
the only files of the benchmark that import ``mxnet_tpu``; they call
the entry points a user calls and hand the benchmark's own weights to
the program."""
