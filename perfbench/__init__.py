"""The repository's benchmark: harness, traffic, references and reducers.

Everything here is the yardstick.  From the program (``chainermn_tpu``) it
takes only the system under test and the events that system hands to an
injected recorder; every clock, count-to-metric reduction, FLOP/byte formula,
reference and tolerance lives in this package.  See ``README.md``.
"""
