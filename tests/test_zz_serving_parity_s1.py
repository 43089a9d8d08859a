"""Shard 1 of 3 of ``test_zz_serving_parity``'s 60-query
corpus, in a file of its own so that ``--dist loadfile`` can hand it to
another worker."""

from test_zz_serving_parity import _CASES, corpus, corpus_test  # noqa: F401

test_prepared_vs_direct_parity = corpus_test(_CASES[1::3])
