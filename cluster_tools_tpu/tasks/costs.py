"""Edge probabilities -> multicut costs.

Re-design of the reference's ``cluster_tools/costs/probs_to_costs.py``
(SURVEY.md §2a "costs"): the classic transform

    w(e) = log((1 - p_e) / p_e) + log((1 - beta) / beta)

with optional edge-size weighting and ignore-label handling.  A single
driver-side task (the reference also ran it as one job): m edges is tiny
next to the volume.  The vectorized transform runs through jax.numpy so the
same code path serves host and device.

Artifact: ``tmp_folder/graph/costs.npy`` (float32 [m]), aligned with
``graph.npz``'s edge list.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import handoff
from ..runtime import trace as trace_mod
from ..runtime.task import BaseTask
from .features import features_path
from .graph import graph_dir, load_global_graph


def costs_path(tmp_folder: str) -> str:
    return os.path.join(graph_dir(tmp_folder), "costs.npy")


#: edges a dispatch of the transform takes: one compiled program whatever
#: the graph's size (a graph of another size compiles nothing new)
_CHUNK = 1 << 16


@partial(jax.jit, static_argnames=("weighted",))
def _costs_program(probs, sizes, largest, beta_term, exponent, eps, weighted):
    """The transform over one chunk of the edge list."""
    with jax.named_scope("rag.costs"):
        p = jnp.clip(probs, eps, 1.0 - eps)
        w = jnp.log((1.0 - p) / p) + beta_term
        if weighted:
            w = w * (sizes / jnp.maximum(largest, 1.0)) ** exponent
    return w


def compute_costs(
    probs: np.ndarray,
    beta: float = 0.5,
    edge_sizes: np.ndarray | None = None,
    weighting_exponent: float = 1.0,
    eps: float = 1e-5,
) -> np.ndarray:
    """The probability->cost transform, vectorized.

    ``beta`` < 0.5 biases toward merging, > 0.5 toward splitting.  With
    ``edge_sizes``, costs are scaled by ``(size / max_size) ** exponent``
    (the reference's 'xy'/size weighting scheme collapsed to its core).

    The edge list goes through one compiled program in chunks of 65,536
    (the last padded), ``beta``, the exponent, ``eps`` and the largest size
    traced scalars.
    """
    n = len(probs)
    weighted = edge_sizes is not None
    largest = np.float32(np.max(edge_sizes)) if weighted and n else np.float32(1.0)
    scalars = (largest, np.float32(np.log((1.0 - beta) / beta)),
               np.float32(weighting_exponent), np.float32(eps))
    out = np.empty(n, np.float32)
    for lo in range(0, n, _CHUNK):
        m = min(_CHUNK, n - lo)
        chunk = np.full(_CHUNK, 0.5, np.float32)
        chunk[:m] = probs[lo:lo + m]
        sizes = np.ones(_CHUNK, np.float32)
        if weighted:
            sizes[:m] = edge_sizes[lo:lo + m]
        w = _costs_program(chunk, sizes, *scalars, weighted=weighted)
        out[lo:lo + m] = np.asarray(w)[:m]
    return out


class ProbsToCostsBase(BaseTask):
    """Transform merged edge features into signed multicut costs.

    Params: ``beta``, ``weighting_scheme`` (None or 'size'),
    ``weighting_exponent``; optional ``ignore_label`` semantics are already
    enforced upstream (label 0 never becomes a graph node).
    """

    task_name = "probs_to_costs"

    @staticmethod
    def default_task_config():
        return {
            "threads_per_job": 1,
            "device_batch": 1,
            "beta": 0.5,
            "weighting_scheme": None,
            "weighting_exponent": 1.0,
        }

    def run_impl(self):
        cfg = self.get_config()
        # fusable edges (features -> costs, graph -> costs): consume the
        # merged features and edge sizes from live in-memory handoffs
        feats = handoff.load_array(features_path(self.tmp_folder))
        _, _, _, sizes = load_global_graph(self.tmp_folder)
        probs = feats[:, 0]
        use_sizes = cfg.get("weighting_scheme") == "size"
        with trace_mod.span("costs.transform", n_edges=len(probs)):
            costs = compute_costs(
                probs,
                beta=float(cfg.get("beta", 0.5)),
                edge_sizes=sizes if use_sizes else None,
                weighting_exponent=float(cfg.get("weighting_exponent", 1.0)),
            )
        self.save_handoff_array(costs_path(self.tmp_folder), costs)
        return {"n_edges": len(costs), "n_attractive": int((costs > 0).sum())}


class ProbsToCostsLocal(ProbsToCostsBase):
    target = "local"


class ProbsToCostsTPU(ProbsToCostsBase):
    target = "tpu"
