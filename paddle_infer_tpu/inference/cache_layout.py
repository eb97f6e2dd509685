"""What one decoder layer keeps per cached token, as the model states it
and the paged engine allocates it.

Two kinds today, the second in two forms:

``kv``      keys and values per head — two pools ``[P, heads, page, dim]``
            (the LLaMA / GPT block; int8 pools are ``(payload, scales)``
            pairs of that shape).
``latent``  one vector per token shared by every head — ONE pool
            ``[P, page, lanes]``, no V pool and no head axis
            (latent attention: the normed low-rank key/value row beside
            its rotated position part).  ``width`` numbers are cached a
            token; ``lanes`` is ``width`` rounded up to the TPU's 128
            lanes, the rest zeros.  A row-major ``[P, page, 576]`` array
            occupies 640 lanes a row on the device anyway, and for a
            last dimension that is no multiple of 128 the TPU's own
            layout makes the PAGE index the fastest dimension (measured
            on the v5e at ``[4097, 16, 576]``): a page would be strewn
            over the whole pool and every program would transpose the
            pool on its way in and out.  Stating the lanes keeps a page
            contiguous and the page write in place.

            With ``index_width`` (a layer whose attention reads only the
            tokens a learned indexer chooses: models/latent_moe.py) the
            layer keeps a SECOND vector a token, the indexer's key, in a
            second pool ``[P, page, index_lanes]`` beside the first and
            under the same block ids.  What a builder of the next
            two-pool latent layer needs to know: the pair travels
            wherever a ``kv`` layer's ``(k, v)`` pair travels — the
            engine's two per-layer lists, the step's donation, the page
            copy of a shared partial block — so the block pool, the
            prefix cache, release and eviction, which deal in block ids
            alone, carry both by one table and needed no change; the
            layer's cache tuple gains the second pool right behind the
            first (``step_cache``); and everything that serialises or
            scales a layer's pools AS A ``(k, v)`` PAIR OF EQUAL SHAPE
            (an int8 pool's per-head scales, the host tier's park and
            resume, the handoff between replicas) or verifies drafts
            through per-head lanes, or splits a head axis over ``mp``,
            is refused at start-up, one sentence each
            (``serving/sharded/mesh.validate_cache_layout``).

            A decoder layer with TWO attention sub-layers
            (models/longcat_flash.py) states two entries, ``part`` 0 and
            1, each a one-pool latent layer of its own: the layout's
            length is the number of CACHE layers (attention sub-layers),
            not of decoder layers, and everything that walks pools walks
            the layout.  Block ids are shared as ever, so the page
            writer, the prefix cache, release and eviction carry all of
            them under the one table; what a latent pool cannot do yet
            it cannot do for either part.

A model states its layers' kinds through ``cache_layout()`` (a list, one
entry a cache layer); a model without it is the ``kv`` case at its
config's heads.  ``PagedGenerationEngine._ensure_pages``, ``run_paged_program``
and the serving programs (``serving/programs.py``) go through this one
description, so the pools still travel as the ``(k_pages, v_pages)``
pair of per-layer lists every program donates — a ``latent`` layer's
entry in the second list is ``None``, an empty pytree, unless it states
an ``index_width``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional


@dataclasses.dataclass(frozen=True)
class LayerCache:
    kind: str                 # "kv" | "latent"
    heads: int = 0            # kv
    dim: int = 0              # kv
    width: int = 0            # latent
    index_width: int = 0      # latent: the indexer's key, second pool
    part: int = 0             # which attention sub-layer of its decoder
                              # layer this entry caches (0: the first or
                              # the only one)

    @classmethod
    def kv(cls, heads: int, dim: int) -> "LayerCache":
        return cls("kv", heads=int(heads), dim=int(dim))

    @classmethod
    def latent(cls, width: int, index_width: int = 0,
               part: int = 0) -> "LayerCache":
        return cls("latent", width=int(width), index_width=int(index_width),
                   part=int(part))

    @property
    def lanes(self) -> int:
        """``width`` rounded up to whole 128-lane tiles (latent)."""
        return -(-self.width // 128) * 128

    @property
    def index_lanes(self) -> int:
        """``index_width`` rounded up the same way; 0 without one."""
        return -(-self.index_width // 128) * 128

    @property
    def one_pool(self) -> bool:
        """A latent layer without an index: no second pool."""
        return self.kind == "latent" and not self.index_width

    def pool_shapes(self, num_pages: int, page: int):
        """Shapes of the (first, second) pool; the second is None for a
        layer that keeps one vector a token."""
        if self.kind == "latent":
            return (num_pages, page, self.lanes), (
                (num_pages, page, self.index_lanes) if self.index_width
                else None)
        shape = (num_pages, self.heads, page, self.dim)
        return shape, shape

    def values_per_token(self) -> int:
        """Numbers cached per token in this layer (both pools)."""
        if self.kind == "latent":
            return self.width + self.index_width
        return 2 * self.heads * self.dim

    def stored_per_token(self) -> int:
        """Pool elements per token in this layer: the cached numbers and,
        for a latent row, its lane padding."""
        if self.kind == "latent":
            return self.lanes + self.index_lanes
        return 2 * self.heads * self.dim

    def head_axis(self) -> Optional[int]:
        """The pool axis a serving mesh may split over "mp"."""
        return None if self.kind == "latent" else 1

    # ------------------------------------------------ the step's tuples
    def step_cache(self, first, second, *rest):
        """The cache tuple one layer is handed inside a serving program:
        its pool(s) first, then the step's shared arrays."""
        if self.one_pool:
            return (first, *rest)
        return (first, second, *rest)

    def pools_of(self, cache):
        """(first, second) pools back out of a layer's returned tuple."""
        if self.one_pool:
            return cache[0], None
        return cache[0], cache[1]


def layout_of(model) -> List[LayerCache]:
    """The model's own statement, or the ``kv`` case at its config."""
    stated = getattr(model, "cache_layout", None)
    if callable(stated):
        return list(stated())
    cfg = model.config
    return [LayerCache.kv(cfg.num_attention_heads,
                          cfg.hidden_size // cfg.num_attention_heads)
            ] * int(cfg.num_hidden_layers)


def has_latent(layout) -> bool:
    return any(c.kind == "latent" for c in layout)


def has_index(layout) -> bool:
    """Whether a latent layer keeps an indexer's key beside its row."""
    return any(c.kind == "latent" and c.index_width for c in layout)
