"""The mask of diffusion over blocks (BD3-LM, arXiv:2503.09573; SDAR,
arXiv:2510.06303) on packed documents, as data and by its definition.

A sequence of L tokens is fed as 2L rows, the noised copy ``[0, L)`` and
then the clean copy ``[L, 2L)``, and inside a document cut into blocks of
``block`` tokens

- a noisy row of block b sees the noisy rows of block b, both ways;
- a noisy row of block b sees the clean rows of the blocks before b;
- a clean row of block b sees the clean rows of blocks up to and with b,
  and no noisy key.

``BlockDiffMask`` holds the documents (``masks.Mask`` of their causal
packing: the loader's view) and the exact area of the doubled mask;
``allowed`` is the dense boolean from each row's and key's half,
document and block index, never from a slice list. The slices the
program runs come from its own ``api.infer_block_diffusion_mask``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import masks


def blockdiff_area(doc_lengths, block: int) -> int:
    """Allowed pairs of the doubled mask: a document of n tokens in
    blocks of B has (n/B)(n/B + 1)/2 block pairs clean -> clean, (n/B)
    (n/B - 1)/2 noisy -> clean and n/B on the noisy diagonal, B^2 pairs
    each: n^2 + n B."""
    return sum(n * n + n * block for n in doc_lengths)


@dataclasses.dataclass(frozen=True)
class BlockDiffMask:
    documents: masks.Mask  # the packed documents, data tokens
    block: int

    @property
    def data_tokens(self) -> int:
        return self.documents.total

    @property
    def rows(self) -> int:
        return 2 * self.documents.total

    @property
    def doc_lengths(self):
        return self.documents.doc_lengths

    @property
    def cu_seqlens(self) -> list[int]:
        return self.documents.cu_seqlens

    @property
    def area(self) -> int:
        return blockdiff_area(self.doc_lengths, self.block)

    def describe(self) -> dict:
        rows = self.rows
        return {
            "type": "block_diffusion",
            "data_tokens": self.data_tokens,
            "rows": rows,
            "block": self.block,
            "docs": len(self.doc_lengths),
            "area": self.area,
            "causal_share_pct": 100.0 * self.area / (rows * (rows + 1) // 2),
        }


def build_mask(spec: dict, data_tokens: int, block: int) -> BlockDiffMask:
    """``spec``: a ``varlen_block_causal`` mask of ``masks.build_mask``
    over the data tokens; every document a whole number of blocks."""
    documents = masks.build_mask(spec, data_tokens, index=0)
    ragged = [n for n in documents.doc_lengths if n % block]
    if ragged:
        raise ValueError(
            f"documents of {ragged} tokens are no whole number of blocks "
            f"of {block}"
        )
    return BlockDiffMask(documents, int(block))


def allowed(mask: BlockDiffMask, q_rows, k_rows):
    """Boolean [len(q_rows), len(k_rows)] of the doubled mask, from row
    numbers in ``[noisy ; clean]`` order (numpy or jax.numpy arrays)."""
    if isinstance(q_rows, np.ndarray):
        xp = np
    else:
        import jax.numpy as xp
    n = mask.data_tokens
    cu = xp.asarray(np.asarray(mask.cu_seqlens))

    def parts(rows):
        clean = rows >= n
        token = rows - xp.where(clean, n, 0)
        doc = xp.searchsorted(cu[1:], token, side="right")
        return clean, doc, (token - cu[doc]) // mask.block

    cq, dq, bq = (a[:, None] for a in parts(q_rows))
    ck, dk, bk = (a[None, :] for a in parts(k_rows))
    return (dq == dk) & (
        (~cq & ~ck & (bq == bk)) | (~cq & ck & (bk < bq)) | (cq & ck & (bk <= bq))
    )
