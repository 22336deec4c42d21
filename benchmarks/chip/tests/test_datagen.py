"""The benchmark's copy of the client token generator gives the program
generator's tokens at the same seed."""

import numpy as np

import datagen
from repro.data.synthetic import make_hetero_lm_dataset


def test_segment_matches_program_generator():
    vocab, clients, seq, batch, tau, seed = 97, 3, 12, 2, 2, 1234
    ds = make_hetero_lm_dataset(vocab, clients, seq, batch,
                                heterogeneity=0.8, seed=seed)
    seg = datagen.segment(5, seed, vocab=vocab, n_clients=clients, tau=tau,
                          batch=batch, seq_len=seq, heterogeneity=0.8,
                          rounds=3)
    assert seg.shape == (3, tau, clients, batch, seq)
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(seg[i]),
                                      np.asarray(ds.sample_round(5 + i, tau)))
