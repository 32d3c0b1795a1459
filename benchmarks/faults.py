"""The timed path broken underneath, for the tests and for the readings
that the limits of ``correct`` are held against (``python -m
benchmarks.readings --faults``).  Each is a wrapper ``run_cell`` /
``setup`` puts around the engine (``wrap_engine``); the benchmark's own
runs never use one."""

import jax
import jax.numpy as jnp
import numpy as np


class _Wrapped:
    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)


class FrozenStep(_Wrapped):
    """A training step that returns its state unchanged."""

    def train_batch(self, data_iter):
        e = self._engine
        keep = jax.tree_util.tree_map(jnp.copy, dict(e.state))
        params = jax.tree_util.tree_map(jnp.copy, e._module_params)
        loss = e.train_batch(data_iter)
        e.state.update(keep)
        e._module_params = params
        return loss


class HalfBatch(_Wrapped):
    """A training step that leaves half of the batch's labelled positions
    out: the second half of the rows carries no MLM label, so the MLM loss
    and its gradient are the first half's.  (The next-sentence labels stay:
    the program's loss has no way to leave one out.)"""

    LABELS = {"masked_lm_labels": -100}

    def train_batch(self, data_iter):
        batch = dict(next(data_iter))
        rows = next(iter(batch.values())).shape[0]
        for key, ignored in self.LABELS.items():
            if key in batch:
                batch[key] = np.array(batch[key])
                batch[key][rows // 2:] = ignored
        return self._engine.train_batch(iter([batch]))


class AlteredToken(_Wrapped):
    """A served token altered where it is produced: each request's second
    token (the cache then holds it, and the request goes on from it)."""

    def __init__(self, engine, vocab=512):
        super().__init__(engine)
        self._altered, self._vocab = set(), vocab

    def step(self):
        done = self._engine.step()
        for r in self._engine.scheduler.slots:
            if (r is not None and len(r.generated) == 2
                    and r.request_id not in self._altered):
                r.generated[-1] = (r.generated[-1] + 1) % self._vocab
                self._altered.add(r.request_id)
        return done


TRAIN_FAULTS = {"frozen_step": FrozenStep, "half_batch": HalfBatch}
