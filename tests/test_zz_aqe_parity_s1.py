"""Shard 1 of 3 of ``test_zz_aqe_parity``'s 60-query
corpus, in a file of its own so that ``--dist loadfile`` can hand it to
another worker."""

from test_zz_aqe_parity import _CASES, corpus, corpus_test  # noqa: F401

test_adaptive_on_off_parity = corpus_test(_CASES[1::3])
