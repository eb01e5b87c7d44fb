"""Seeded synthetic token streams for a token workload (no reference
counterpart: the reference has no text task). What a cross-silo fine-tuning
population looks like, as far as the round's cost and the attack's mechanics
care: documents of heavy-tailed length packed into rows of `seq_len` tokens
without padding, and topics skewed by client.

- `token_sources` seeded sources, each a Zipf unigram law over its own
  permutation of the vocabulary plus a bigram rule (with probability
  `BIGRAM_P` the next token is an affine function of the last one): enough
  structure for a model to learn, and a different one for every source;
- a client draws its topic mixture from Dirichlet(`dirichlet_alpha`) over the
  sources; each of its documents takes one topic and a log-normal length
  (median `doc_len_median`, cut at `seq_len`), starts with token 0, and the
  documents fill the client's `sequences_per_client` rows back to back;
- the held-out rows are drawn the same way from the uniform mixture.

Everything is drawn in bulk with numpy: a population of a few hundred rows
takes well under a second.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from dba_mod_tpu import config as cfg

DOC_START = 0  # every document's first token
DOC_LEN_SIGMA = 1.0  # of the log-normal document lengths
BIGRAM_P = 0.5  # how often a token follows from the one before it


@dataclasses.dataclass
class TokenData:
    train_tokens: np.ndarray   # [N, T] int32
    test_tokens: np.ndarray    # [M, T] int32
    client_rows: Dict[int, List[int]]  # participant -> its rows of train_tokens
    vocab_size: int


def _layout(rng: np.random.RandomState, n_rows: int, seq_len: int,
            mixture: np.ndarray, cdfs: np.ndarray, median: float):
    """Documents laid over `n_rows` rows: (tokens before the bigram chain,
    which positions follow their predecessor, each position's source), all
    [n_rows, seq_len]."""
    total = n_rows * seq_len
    vocab = cdfs.shape[1]
    # document lengths until the rows are full
    lengths: List[np.ndarray] = []
    have = 0
    while have < total:
        draw = np.clip(rng.lognormal(np.log(median), DOC_LEN_SIGMA,
                                     size=max(16, 2 * total // int(median))),
                       2, seq_len).astype(np.int64)
        lengths.append(draw)
        have += int(draw.sum())
    lengths = np.concatenate(lengths)
    n_docs = int(np.searchsorted(np.cumsum(lengths), total)) + 1
    lengths = lengths[:n_docs]
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    topic = np.repeat(rng.choice(len(mixture), size=n_docs, p=mixture),
                      lengths)[:total]
    is_start = np.zeros((total,), bool)
    is_start[starts[starts < total]] = True
    # unigram draws by inverse CDF, one source at a time
    uniform = rng.random_sample(total)
    unigram = np.empty((total,), np.int64)
    for s in np.unique(topic):
        at = topic == s
        unigram[at] = np.minimum(np.searchsorted(cdfs[s], uniform[at]),
                                 vocab - 1)
    follow = ((rng.random_sample(total) < BIGRAM_P)
              & ~is_start).reshape(n_rows, seq_len)
    follow[:, 0] = False  # a row's first token has no predecessor in the row
    return (np.where(is_start, DOC_START, unigram).reshape(n_rows, seq_len),
            follow, topic.reshape(n_rows, seq_len))


def _chain(tok: np.ndarray, follow: np.ndarray, a: np.ndarray, b: np.ndarray,
           vocab: int) -> np.ndarray:
    """The bigram rule over positions, all rows at once: where a position
    follows, its token is an affine function of the one before it."""
    for t in range(1, tok.shape[1]):
        nxt = 1 + (a[:, t] * tok[:, t - 1] + b[:, t]) % (vocab - 1)
        tok[:, t] = np.where(follow[:, t], nxt, tok[:, t])
    return tok.astype(np.int32)


def load_token_dataset(params: cfg.Params, vocab_size: int) -> TokenData:
    """The population of a token workload, from `params.random_seed` alone."""
    seed = int(params.get("random_seed", 1))
    rng = np.random.RandomState(seed)
    seq_len = int(params["seq_len"])
    n_sources = int(params["token_sources"])
    participants = int(params["number_of_total_participants"])
    per_client = int(params["sequences_per_client"])
    median = float(params["doc_len_median"])
    # sources: Zipf over a permutation of the ids 1..V-1 (0 starts documents)
    ranks = np.arange(1, vocab_size, dtype=np.float64)
    zipf = 1.0 / ranks
    zipf /= zipf.sum()
    cdfs = np.empty((n_sources, vocab_size))
    for s in range(n_sources):
        law = np.zeros((vocab_size,))
        law[1 + rng.permutation(vocab_size - 1)] = zipf
        cdfs[s] = np.cumsum(law)
    mult = 1 + 2 * rng.randint(1, vocab_size // 2, size=n_sources)
    shift = rng.randint(0, vocab_size, size=n_sources)
    draw = lambda n, mix: _layout(rng, n, seq_len, mix, cdfs, median)
    alpha = float(params["dirichlet_alpha"])
    parts, client_rows = [], {}
    for c in range(participants):
        client_rows[c] = list(range(c * per_client, (c + 1) * per_client))
        parts.append(draw(per_client, rng.dirichlet(alpha * np.ones(n_sources))))
    n_test = int(params["test_sequences"])
    parts.append(draw(n_test, np.full((n_sources,), 1.0 / n_sources)))
    tok, follow, topic = (np.concatenate(x) for x in zip(*parts))
    tok = _chain(tok, follow, mult[topic], shift[topic], vocab_size)
    return TokenData(train_tokens=tok[:-n_test], test_tokens=tok[-n_test:],
                     client_rows=client_rows, vocab_size=vocab_size)
