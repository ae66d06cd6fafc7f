"""``ops/cache_layout.py``: the one description of what a layer caches.

Over every preset of ``BUILTIN_SPECS`` at its test size, on shapes alone: the
vocabulary holds every leaf ``make_kv_cache`` gives, the account's kinds sum to
the cache's bytes, an unknown leaf raises in the account and in the refusal,
and each whole-row path either holds what the cache holds or has a row that
says why not.
"""

import jax
import pytest

from trlx_tpu.models.transformer import BUILTIN_SPECS, make_kv_cache
from trlx_tpu.ops.cache_layout import KINDS, KV, PATHS, REFUSED, VOCABULARY, cache_bytes, cache_slots, describe, refuse, ring
from trlx_tpu.ops.paged_kv import kv_bytes

ROWS, SLOTS = 2, 24  # past every test preset's window (5 to 9), so that a window layer's cache is a ring
# what passes today: per-head K and V everywhere, and a ring of them through speculation's verify (`_ring_plan`)
HELD = {path: {KV, ring(KV)} if path == "speculative" else {KV} for path in PATHS}
KEYS = [*KINDS, *(ring(kind) for kind in KINDS if any(leaf.kind == kind and leaf.slot_axis for leaf in VOCABULARY.values()))]


@pytest.mark.parametrize("family", sorted(BUILTIN_SPECS))
def test_every_presets_cache_is_described_counted_and_held_or_refused_by_a_row(family):
    cfg = BUILTIN_SPECS[family]("note-test" if family == "dots3" else "test")
    cache = jax.eval_shape(lambda: make_kv_cache(cfg, ROWS, SLOTS))
    layers = cache if isinstance(cache, list) else [cache]
    assert {leaf.name for leaf in describe(cache)} == {name for layer in layers for name in layer} <= set(VOCABULARY)
    assert all(cache_slots(layer) in (None, SLOTS, min(SLOTS, (cfg.layer_layout(i).window or SLOTS) + cfg.mtp_layers)) for i, layer in enumerate(layers))
    held = cache_bytes(cache, SLOTS)
    assert set(held) <= set(KEYS) and sum(held.values()) == kv_bytes(cache) > 0
    some = next(layer for layer in layers if layer)  # (a layer without a sequence mixer caches nothing: nemotron_h's last)
    odd = [*layers[:-1], {**layers[-1], "k_new": some[next(iter(some))]}]
    with pytest.raises(ValueError, match=r"leaves \['k_new'\] that ops/cache_layout.py::VOCABULARY does not know"):
        cache_bytes(odd, SLOTS)
    for path in PATHS:
        with pytest.raises(ValueError, match="k_new"):
            refuse(odd, path, SLOTS)
        refused = [key for key in KEYS if held[key] and key not in HELD[path]]
        rows = [REFUSED.get((path, key)) or REFUSED[path, key.removesuffix(" ring")] for key in refused]  # no row: KeyError
        if not refused:
            refuse(cache, path, SLOTS)
            continue
        with pytest.raises(NotImplementedError, match=rf"^{path} does not support a model whose cache holds .*; use the plain sampler$") as said:
            refuse(cache, path, SLOTS)
        assert all(why in str(said.value) and f"(ROADMAP.md queue 2, {item})" in str(said.value) for why, item in rows)
        assert all(KINDS[key.removesuffix(" ring")] in str(said.value) for key in refused)


def test_every_path_holds_a_kind_or_has_a_row_for_it_and_the_table_has_no_other_row():
    """The table is exactly what is refused today: lifting a path for a kind
    deletes a row here and adds the pair to ``HELD`` above."""
    for path in PATHS:
        for key in KEYS:
            kind = key.removesuffix(" ring")
            assert (key in HELD[path]) != ((path, key) in REFUSED or (path, kind) in REFUSED), (path, key)
    assert {path for path, _ in REFUSED} == set(PATHS) and {key for _, key in REFUSED} <= set(KEYS)
    assert all(why and item[0] == "B" for why, item in REFUSED.values())
    with pytest.raises(ValueError, match="unknown rollout path 'sampler'"):
        refuse([], "sampler", SLOTS)
