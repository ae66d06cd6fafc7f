"""Pipeline abstractions + registry.

Reference: ``trlx/pipeline/__init__.py:9-97``. Instead of torch DataLoaders,
``create_loader`` returns a lightweight host-side ``BatchLoader`` producing
numpy batches (collated to fixed shapes) — the host→device boundary is the
trainer's jitted step, which donates the arrays to the mesh.

Concurrency helpers live alongside the registry: :class:`PrefetchLoader`
(background-thread batch collation) here, and the bounded rollout chunk
pipeline in :mod:`trlx_tpu.pipeline.rollout_pipeline` (device generation
overlapping host reward scoring — docs/PERFORMANCE.md).
"""

import random
import sys
from abc import abstractmethod
from typing import Any, Callable, Dict, Iterable, Iterator, List, Optional

_DATAPIPELINE: Dict[str, type] = {}


def register_datapipeline(name: Any = None) -> Callable:
    """Decorator registering a pipeline class by name."""

    def register_cls(cls, registered_name: str):
        _DATAPIPELINE[registered_name.lower()] = cls
        setattr(sys.modules[__name__], registered_name, cls)
        return cls

    if isinstance(name, type):
        return register_cls(name, name.__name__)

    def wrap(cls):
        return register_cls(cls, name if isinstance(name, str) else cls.__name__)

    return wrap


def get_pipeline(name: str) -> type:
    name = name.lower()
    if name in _DATAPIPELINE:
        return _DATAPIPELINE[name]
    raise ValueError(f"Unknown pipeline '{name}'. Registered: {sorted(_DATAPIPELINE)}")


class BatchLoader:
    """Minimal host-side batch iterator over an indexable dataset.

    Supports shuffling, drop_last, and a collate function; re-iterable
    (fresh order per epoch when shuffled).

    ``group_key`` (the rollout stores' pad policy, ``ppo_pipeline.py``) forms
    the minibatches of a shuffled epoch from rows of like key: the shuffle is
    drawn as without it, the rows that fill whole batches are stable-sorted
    by key and cut into batches, and the batches are visited in the order in
    which the shuffle first reached one of their rows (a uniform order, and
    no second draw: the partition is a function of the seed and the dataset,
    which emergency resume relies on). The rows left over stay the trailing
    partial batch. Where every key is equal nothing moves: the batches are
    the ungrouped loader's, element for element and in order.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        collate_fn: Callable[[List[Any]], Any],
        shuffle: bool = False,
        drop_last: bool = False,
        seed: int = 0,
        group_key: Optional[Callable[[Any], Any]] = None,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.collate_fn = collate_fn
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.group_key = group_key
        self._rng = random.Random(seed)

    def __len__(self) -> int:
        n = len(self.dataset)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size

    def advance_epoch(self) -> None:
        """Consume one epoch's worth of shuffle randomness without iterating
        — the emergency-resume fast-forward (docs/RESILIENCE.md) skips whole
        epochs but must leave later epochs' shuffle orders exactly where an
        uninterrupted run would have them."""
        if self.shuffle:
            self._rng.shuffle(list(range(len(self.dataset))))

    def _grouped(self, order: List[int]) -> List[List[int]]:
        """``order`` (one epoch's shuffle) as batches of like ``group_key``."""
        bs = self.batch_size
        whole = len(order) - len(order) % bs
        drawn = {idx: pos for pos, idx in enumerate(order)}
        head = sorted(order[:whole], key=lambda i: self.group_key(self.dataset[i]))
        batches = [head[s : s + bs] for s in range(0, whole, bs)]
        batches.sort(key=lambda b: min(drawn[i] for i in b))
        return batches + ([order[whole:]] if whole < len(order) else [])

    def __iter__(self) -> Iterator[Any]:
        order = list(range(len(self.dataset)))
        if self.shuffle:
            self._rng.shuffle(order)
        if self.shuffle and self.group_key is not None:
            batches = self._grouped(order)
        else:
            batches = [
                order[s : s + self.batch_size]
                for s in range(0, len(order), self.batch_size)
            ]
        for idxs in batches:
            if self.drop_last and len(idxs) < self.batch_size:
                return
            yield self.collate_fn([self.dataset[i] for i in idxs])


class PrefetchLoader:
    """Background-thread prefetch over any re-iterable batch loader.

    The torch ``DataLoader(num_workers, prefetch_factor)`` capability the
    reference leans on (SURVEY.md §2.4 "torch C++ data machinery"): a worker
    thread keeps up to ``depth`` collated batches ready while the device
    consumes the current one. Collation bottoms out in the native C++
    ``pad_rows`` (ctypes releases the GIL), so the overlap is real. One
    worker preserves batch order and shuffle determinism; worker exceptions
    re-raise in the consumer.
    """

    def __init__(self, loader, depth: int = 2):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.loader = loader
        self.depth = depth

    def __len__(self) -> int:
        return len(self.loader)

    def __iter__(self) -> Iterator[Any]:
        import queue
        import threading

        q: "queue.Queue" = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        _END, _ERR = object(), object()

        def put(item) -> bool:
            """Enqueue unless the consumer cancelled; never blocks forever."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for batch in self.loader:
                    if not put(batch):
                        return  # cancelled: stop collating, drop the epoch
                put(_END)
            except BaseException as e:  # re-raised in the consumer
                put((_ERR, e))

        t = threading.Thread(target=worker, daemon=True, name="trlx-prefetch")
        t.start()
        try:
            while True:
                item = q.get()
                if item is _END:
                    return
                if isinstance(item, tuple) and len(item) == 2 and item[0] is _ERR:
                    raise item[1]
                yield item
        finally:
            # consumer stopped (early break, exception, or exhaustion): cancel
            # the worker between batches rather than draining a whole epoch
            stop.set()
            try:
                q.get_nowait()  # unblock a put in flight
            except queue.Empty:
                pass
            try:
                t.join(timeout=5)
            except Exception:
                # interpreter shutdown: an infinite prompt iterator holding
                # this loader is GC'd after threading's teardown — the daemon
                # worker is already dead, the join just can't say so
                pass


class BasePipeline:
    """An indexable dataset of prompts/samples."""

    def __init__(self, path: str = "dataset"):
        self.path = path

    @abstractmethod
    def __getitem__(self, index: int):
        ...

    @abstractmethod
    def __len__(self) -> int:
        ...

    @abstractmethod
    def create_loader(self, batch_size: int, shuffle: bool = False, **kwargs) -> BatchLoader:
        ...


class BaseRolloutStore:
    """A mutable store of collected experiences."""

    def __init__(self, capacity: int = -1):
        self.history: List[Any] = []
        self.capacity = capacity

    @abstractmethod
    def push(self, exps: Iterable[Any]):
        """Push experiences to the store."""
        ...

    def __getitem__(self, index: int):
        return self.history[index]

    def __len__(self) -> int:
        return len(self.history)

    @abstractmethod
    def create_loader(self, batch_size: int, shuffle: bool = False, **kwargs) -> BatchLoader:
        ...
