"""A ColD Fusion contributor: a party with a private dataset that downloads
the base model, finetunes it locally (paper §3 — any loss-minimizing
procedure), and uploads the result.  The classification head stays private
(per-dataset heads, §4.2); only the shared body is contributed.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import encoder as E
from repro.train import finetune as FT


@dataclass
class Contributor:
    cfg: ArchConfig
    task_id: int
    num_classes: int
    x: np.ndarray
    y: np.ndarray
    steps: int = 30
    batch_size: int = 32
    lr: float = 5e-4
    seed: int = 0
    # Private head persists across iterations (re-initialized heads also
    # work; persistent heads converge faster — flagged in EXPERIMENTS.md).
    reset_head_each_iter: bool = False
    # Compute a diagonal Fisher alongside the contribution (enables the
    # Repository's fusion_op="fisher"; Matena & Raffel 2021, paper §8).
    with_fisher: bool = False
    last_fisher: Optional[Dict] = field(default=None, repr=False)
    # finetune metrics of the latest contribute() ({"loss": [...], ...})
    last_metrics: Optional[Dict] = field(default=None, repr=False)
    _head: Optional[Dict] = field(default=None, repr=False)
    _iter: int = 0

    def _ensure_head(self):
        if self._head is None or self.reset_head_each_iter:
            key = jax.random.PRNGKey((self.seed, self.task_id, self._iter)[0] * 7919 + self.task_id * 131 + self._iter)
            self._head = E.init_cls_head(self.cfg, key, self.num_classes)
        return self._head

    def contribute(self, base_body) -> Dict:
        """One ColD iteration: finetune the downloaded base on local data and
        return the updated body (the upload)."""
        head = self._ensure_head()
        body, head, self.last_metrics = FT.finetune(
            self.cfg, base_body, head, self.x, self.y,
            steps=self.steps, batch_size=self.batch_size, lr=self.lr,
            seed=self.seed * 1000 + self._iter,
        )
        self._head = head
        if self.with_fisher:
            self.last_fisher = FT.compute_fisher(
                self.cfg, body, head, self.x, self.y, seed=self.seed)
        self._iter += 1
        return body
