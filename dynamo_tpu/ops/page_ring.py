"""The ring of VMEM blocks and the chain of page copies that the first-party
decode kernels share (ops/latent_attention.py, ops/gqa_attention.py,
ops/mha_attention.py).

A kernel's grid step is one lane, which walks its own ``ceil(tokens / (N
page_tokens))`` KV blocks of ``N`` pages. Every (lane, block) of the call is
one LINK of a chain through a ring ``buf [K, N, rows, w]`` of ``K`` blocks: a
link's pages are asked for ``K - 1`` links before it is computed on, across
lanes and grid steps alike, so that ``(K - 1) N`` pages are in flight
whatever one block's arithmetic takes, and a short lane's fetch hides behind
its neighbour's products. The pages stay in HBM (``pages_ref [n_pages, rows,
w]``) and come a page a DMA through the block table, which with the lengths
arrives by scalar prefetch.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def page_chain(tokens, tables_ref, pages_ref, buf, sems, *, width: int,
               page_tokens: int, lanes):
    """``(each_page, fetch)`` over the ring ``buf`` with one DMA semaphore a
    block in ``sems``.

    ``tokens(lane)`` is the scalar count of tokens ``lane`` holds (at least
    1: a lane has a link of its own, or the chain and its reader part),
    ``tables_ref`` the SMEM tables ``[lanes * width]`` lane after lane, and
    ``lanes`` how many lanes the chain runs through (a Python int or a traced
    scalar: lanes at and past it are never fetched)."""
    K, N = buf.shape[:2]
    span = N * page_tokens

    def each_page(lane, blk, slot, wait: bool):
        """Start, or await, the copy of every page of block ``blk`` that
        ``lane`` has. Where the block is whole no page is tested, and one
        wait (for as many bytes as the buffer holds) awaits them all."""
        first = blk * N
        have = pl.cdiv(tokens(lane), page_tokens) - first
        entry = lane * width + first

        def copy(p, page):
            return pltpu.make_async_copy(pages_ref.at[page], buf.at[slot, p], sems.at[slot])

        @pl.when(have >= N)
        def _():
            if wait:
                # (a wait reads its descriptor's size alone)
                pltpu.make_async_copy(buf.at[slot], buf.at[slot], sems.at[slot]).wait()
            else:
                # every entry read before the first copy starts: a start is a
                # fence to the scheduler, the reads and their sums are not
                ids = [tables_ref[entry + p] for p in range(N)]
                for p in range(N):
                    copy(p, ids[p]).start()

        @pl.when(have < N)
        def _():
            for p in range(N):
                @pl.when(p < have)
                def _(p=p):
                    c = copy(p, 0 if wait else tables_ref[entry + p])
                    c.wait() if wait else c.start()

    def fetch(ahead):
        """Ask for the link the fetch cursor ``ahead`` = (lane, block,
        slot) stands on, if there is one, and move it on a link."""
        f_lane, f_blk, f_slot = ahead
        pl.when(f_lane < lanes)(lambda: each_page(f_lane, f_blk, f_slot, False))
        more = f_blk + 1 < pl.cdiv(tokens(jnp.minimum(f_lane, lanes - 1)), span)
        return (jnp.where(more, f_lane, jnp.minimum(f_lane + 1, lanes)),
                jnp.where(more, f_blk + 1, 0),
                jnp.where(f_slot + 1 == K, 0, f_slot + 1))

    return each_page, fetch


def start_chain(fetch, buf, ring_ref):
    """A call's first grid step: zero the ring (a page that was never asked
    for is multiplied by a weight of 0: it has to hold numbers), ask for the
    first ``K - 1`` links and leave the ring's state (slot to compute on,
    then the fetch cursor) in ``ring_ref`` SMEM ``[4]``."""
    buf[...] = jnp.zeros(buf.shape, buf.dtype)
    ahead = (0, 0, 0)
    for _ in range(buf.shape[0] - 1):
        ahead = fetch(ahead)
    ring_ref[0] = 0
    for i in range(3):
        ring_ref[1 + i] = ahead[i]
