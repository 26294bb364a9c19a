"""Model hosting: load/unload model params with HBM accounting.

Replaces the reference's in-actor model registry loading
(``293-project/src/scheduler.py:374-421`` torchvision → ``cuda:0``,
``:499-515`` unload via ``cpu()+del+empty_cache`` / load on hot-swap).
On TPU there is no allocator cache to flush: params are device arrays; when
the last reference drops, XLA frees the HBM. Loading restores from an orbax
checkpoint when one exists, else initializes from seed (the reference's
"reload from registry" behavior).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional, Tuple

import jax

from ray_dynamic_batching_tpu.models.base import ServableModel, get_model
from ray_dynamic_batching_tpu.utils.logging import get_logger

logger = get_logger("host")


class ModelHost:
    """Reference-counted (model → params) cache for one process."""

    def __init__(self, checkpoint_dir: Optional[str] = None, seed: int = 0,
                 model_kwargs: Optional[Dict[str, Dict[str, Any]]] = None):
        from ray_dynamic_batching_tpu.utils import compile_cache

        compile_cache.enable()  # repeat bucket compiles become disk hits
        self.checkpoint_dir = checkpoint_dir
        self.seed = seed
        self.model_kwargs = model_kwargs or {}
        self._loaded: Dict[str, Tuple[ServableModel, Any]] = {}
        self._refcounts: Dict[str, int] = {}
        self._lock = threading.Lock()

    def _load_params(self, model: ServableModel):
        if self.checkpoint_dir:
            path = os.path.join(self.checkpoint_dir, model.name)
            if os.path.isdir(path):
                try:
                    import orbax.checkpoint as ocp

                    ckptr = ocp.StandardCheckpointer()
                    abstract = jax.eval_shape(
                        lambda: model.init(jax.random.PRNGKey(self.seed))
                    )
                    params = ckptr.restore(os.path.abspath(path), abstract)
                    logger.info("%s: restored checkpoint from %s", model.name, path)
                    return params
                except Exception as e:  # noqa: BLE001
                    logger.warning(
                        "%s: checkpoint restore failed (%s); initializing",
                        model.name, e,
                    )
        return model.init(jax.random.PRNGKey(self.seed))

    def acquire(self, name: str) -> Tuple[ServableModel, Any]:
        """Load (or re-reference) a model; returns (model, params)."""
        with self._lock:
            if name in self._loaded:
                self._refcounts[name] += 1
                return self._loaded[name]
        model = get_model(name, **self.model_kwargs.get(name, {}))
        params = self._load_params(model)
        with self._lock:
            if name not in self._loaded:  # lost no race: idempotent either way
                self._loaded[name] = (model, params)
                self._refcounts[name] = 0
            self._refcounts[name] += 1
            return self._loaded[name]

    def release(self, name: str) -> None:
        """Drop one reference; frees HBM when the last holder releases."""
        with self._lock:
            if name not in self._refcounts:
                return
            self._refcounts[name] -= 1
            if self._refcounts[name] <= 0:
                del self._loaded[name]
                del self._refcounts[name]
                logger.info("%s: unloaded (HBM freed on GC)", name)

    def loaded_models(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._refcounts)

    def save_checkpoint(self, name: str, out_dir: Optional[str] = None) -> str:
        """Persist params with orbax (control-plane checkpoint/resume story)."""
        import orbax.checkpoint as ocp

        with self._lock:
            if name not in self._loaded:
                raise KeyError(f"{name} not loaded")
            _, params = self._loaded[name]
        base = out_dir or self.checkpoint_dir
        if base is None:
            raise ValueError("no checkpoint_dir configured")
        path = os.path.abspath(os.path.join(base, name))
        ckptr = ocp.StandardCheckpointer()
        ckptr.save(path, params, force=True)
        ckptr.wait_until_finished()
        return path
