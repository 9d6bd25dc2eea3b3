"""The one general traffic generator: whole batch jobs, back to back.

A cell's traffic is data, in ``benchmark/workloads/<cell>.json`` under
``"traffic"``; this module turns it and ``--seed`` into an endless sequence
of jobs.  Every seed gives the same set of job sizes, in another order, on
other data.

Parameters (all of them):

``volume_shape``   extent of every input volume in the store
``block_shape``    the block grid that ROIs are counted in
``cells``          Voronoi cells of a volume, their centres drawn uniformly
                   from the seed (``benchmark/data.py``)
``volumes``        input volumes kept in the store; jobs walk them in turn
``roi_blocks``     ``null``: a job is a whole volume.  ``[bz, by, bx]``: a job
                   is one ROI of that many blocks; the ROIs of a volume are
                   disjoint, tile it from the origin, and are walked in an
                   order drawn from the seed
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Iterator, List, Optional, Tuple

from .data import fold_seed


@dataclass(frozen=True)
class Job:
    index: int                      # 0, 1, ... in the order they are run
    volume: int                     # which input volume of the store
    roi_begin: Optional[Tuple[int, int, int]]
    roi_end: Optional[Tuple[int, int, int]]
    shape: Tuple[int, int, int]     # extent of the labels the job must store

    @property
    def voxels(self) -> int:
        return self.shape[0] * self.shape[1] * self.shape[2]


def rois_of_volume(traffic: dict) -> List[Tuple[Tuple[int, ...], Tuple[int, ...]]]:
    """Disjoint ROIs of ``roi_blocks`` blocks, tiling from the origin."""
    shape = traffic["volume_shape"]
    block = traffic["block_shape"]
    span = [r * b for r, b in zip(traffic["roi_blocks"], block)]
    slots = [range(0, s - sp + 1, sp) for s, sp in zip(shape, span)]
    return [
        (tuple(lo), tuple(l + sp for l, sp in zip(lo, span)))
        for lo in itertools.product(*slots)
    ]


def jobs(traffic: dict, seed: int) -> Iterator[Job]:
    n_vol = int(traffic["volumes"])
    shape = tuple(traffic["volume_shape"])
    if traffic.get("roi_blocks") is None:
        for i in itertools.count():
            yield Job(i, i % n_vol, None, None, shape)
    rois = rois_of_volume(traffic)
    if not rois:
        raise ValueError("roi_blocks does not fit volume_shape")
    order = fold_seed(seed, 2).permutation(len(rois))
    for i in itertools.count():
        lo, hi = rois[int(order[i % len(rois)])]
        yield Job(i, (i // len(rois)) % n_vol, lo, hi,
                  tuple(h - l for l, h in zip(lo, hi)))
