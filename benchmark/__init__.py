"""The benchmark: harness, traffic, references and the yardstick's
arithmetic.  Nothing under ``mxnet_tpu/`` imports this package, and only
the drivers and the family adapters import the program."""
