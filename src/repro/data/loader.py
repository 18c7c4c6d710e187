"""Sharded, prefetching data loader.

``ShardedLoader`` slices each deterministic global batch to this host's rows
of the (pod, data) mesh axes and device_puts with the right sharding;
``Prefetcher`` overlaps host-side generation with device compute (a bounded
background thread — the standard input-pipeline overlap trick, and one of the
straggler mitigations: a slow host never stalls more than `depth` steps).
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterator

import jax
from jax.profiler import TraceAnnotation


class ShardedLoader:
    """make_batch(step) -> pytree of np/jnp arrays with leading global-batch
    axis; the loader yields device-sharded batches step by step."""

    def __init__(self, make_batch: Callable[[int], Any], mesh=None,
                 batch_axes: tuple[str, ...] = ('data',), start_step: int = 0):
        self.make_batch = make_batch
        self.mesh = mesh
        self.batch_axes = batch_axes
        self.step = start_step

    def _shard(self, batch):
        if self.mesh is None:
            return batch
        from jax.sharding import NamedSharding, PartitionSpec as P

        def put(x):
            spec = P(self.batch_axes) if getattr(x, 'ndim', 0) >= 1 else P()
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        return jax.tree.map(put, batch)

    def __iter__(self) -> Iterator[Any]:
        return self

    def __next__(self):
        batch = self._shard(self.make_batch(self.step))
        self.step += 1
        return batch

    def state_dict(self):
        return {'step': self.step}

    def load_state_dict(self, state):
        self.step = int(state['step'])


class Prefetcher:
    """Bounded background prefetch over any iterator.

    Each item's production is a ``data.produce`` profiler span on the
    prefetch thread, and each wait for one a ``data.wait`` span on the
    consumer's (docs/tracing.md)."""

    _SENTINEL = object()

    def __init__(self, it: Iterator[Any], depth: int = 2):
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self._err = None
        it = iter(it)

        def worker():
            try:
                while True:
                    with TraceAnnotation('data.produce'):
                        item = next(it, self._SENTINEL)
                    if item is self._SENTINEL:
                        break
                    self.q.put(item)
            except Exception as e:          # surface in consumer thread
                self._err = e
            finally:
                self.q.put(self._SENTINEL)

        self.thread = threading.Thread(target=worker, daemon=True)
        self.thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        with TraceAnnotation('data.wait'):
            item = self.q.get()
        if item is self._SENTINEL:
            if self._err:
                raise self._err
            raise StopIteration
        return item
