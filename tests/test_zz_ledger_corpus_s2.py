"""Shard 2 of 3 of ``test_zz_ledger_corpus``'s 60-query
corpus, in a file of its own so that ``--dist loadfile`` can hand it to
another worker."""

from test_zz_ledger_corpus import _CASES, corpus, corpus_test  # noqa: F401

test_corpus_leak_free_under_enforce = corpus_test(_CASES[2::3])
