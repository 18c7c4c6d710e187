"""The traffic's token batches, made from a mix's `stream` parameters.

A copy of the trainer's corpus (`repro.data.synthetic.TokenStream`): each of
`n_domains` domains is a depth-1 Markov chain over the first `sub_vocab` ids,
a share `flip` of tokens is replaced by uniform noise, and the domains listed
in `noisy_domains` emit uniform tokens only. Batch `step` is a pure function
of (seed, step), so the reference rebuilds exactly the rows the trainer saw.
The trainer draws its own batches; the benchmark uses this copy to give the
reference the same rows without taking them from the program.
"""
from __future__ import annotations

import numpy as np


class Stream:
    def __init__(self, params: dict, vocab_size: int, seq_len: int):
        self.p = params
        self.seq_len = seq_len
        self.V = min(vocab_size, params['sub_vocab'])
        rng = np.random.RandomState(params['seed'])
        self.next_tok = rng.randint(0, self.V, size=(params['n_domains'], self.V))

    def batch(self, step: int, batch_size: int, clean_only: bool = False) -> dict:
        p = self.p
        rng = np.random.RandomState(
            (p['seed'] + p['step_mult'] * step + (p['clean_salt'] if clean_only else 0))
            % (2**32 - 1))
        V, S = self.V, self.seq_len
        noisy = p['noisy_domains']
        if clean_only:
            domains = rng.choice([d for d in range(p['n_domains']) if d not in noisy],
                                 batch_size)
        else:
            domains = rng.randint(0, p['n_domains'], batch_size)
        toks = np.empty((batch_size, S + 1), np.int32)
        toks[:, 0] = rng.randint(0, V, batch_size)
        for t in range(S):
            nxt = self.next_tok[domains, toks[:, t]]
            noise = rng.randint(0, V, batch_size)
            flip = rng.rand(batch_size) < p['flip']
            nxt = np.where(flip, noise, nxt)
            nxt = np.where(np.isin(domains, noisy), rng.randint(0, V, batch_size), nxt)
            toks[:, t + 1] = nxt
        return {'inputs': toks[:, :-1], 'labels': toks[:, 1:],
                'domain': domains.astype(np.int32)}

    def outer_batch(self, step: int, batch_size: int) -> dict:
        """The clean batch the trainer's outer step at loop index `step` draws."""
        return self.batch(self.p['outer_step_offset'] + step, batch_size, clean_only=True)
