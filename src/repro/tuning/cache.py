"""Persistent winner cache for the tile autotuner.

One JSON file holds tuned tile configs for any number of machines,
namespaced by hardware fingerprint (core.hw.fingerprint):

    {
      "version": 1,
      "caches": {
        "<fingerprint>": {
          "matmul|4096x4096x4096|float32|pallas": {
            "bm": 512, "bn": 512, "bk": 1024,
            "time_us": 812.4, "baseline_us": 1103.9,
            "speedup": 1.36, "tuned_at": "2026-07-29T12:00:00"
          },
          "flash|2048x2048xd64|bfloat16|pallas": {
            "bq": 512, "bk": 512, ...
          }
        }
      }
    }

Lookups under a fingerprint that is not in the file (new chip, new jax,
interpret-vs-compiled) return None and the caller falls back to the
static chooser in core.blocking — a stale cache can never mis-tile a
different machine. The full format is documented in docs/ARCHITECTURE.md
and EXPERIMENTS.md §Autotune.

This module is import-light on purpose: kernels/ops.py consults it on
every tuned-backend call, so it depends only on repro.core.
"""

from __future__ import annotations

import datetime
import json
import os
import threading
from typing import Any, Optional

import numpy as np

from repro.core import hw
from repro.core.blocking import BlockConfig, FlashBlockConfig, SSDBlockConfig

CACHE_VERSION = 1
CACHE_ENV_VAR = "REPRO_TUNING_CACHE"
DEFAULT_CACHE_PATH = "~/.cache/repro/tuning.json"


def default_cache_path() -> str:
    return os.path.expanduser(os.environ.get(CACHE_ENV_VAR, DEFAULT_CACHE_PATH))


def _backend_tag(backend) -> str:
    """Key component naming the execution backend. Accepts a
    core.policy.Policy (preferred — the tag is its kernel_fingerprint,
    i.e. the execution-relevant backend+interpret fields) or a legacy
    string. The fingerprint of a Policy matches the historical string
    spellings ("pallas", "pallas_interpret"), so caches written before
    the Policy refactor keep serving."""
    fp = getattr(backend, "kernel_fingerprint", backend)
    if not isinstance(fp, str):
        raise TypeError(f"expected Policy or backend string, got "
                        f"{type(backend)}")
    return fp


def matmul_key(m: int, n: int, k: int, dtype, backend,
               epilogue: str = "none") -> str:
    """Fused-epilogue variants are keyed separately: the extra flush-
    phase operand DMA and VPU work shift the optimal tile, so a winner
    tuned for the plain GEMM must not be served to e.g. bias_silu.
    epilogue="none" keeps the historical key so old caches stay valid."""
    key = f"matmul|{m}x{n}x{k}|{np.dtype(dtype).name}|{_backend_tag(backend)}"
    if epilogue not in (None, "none"):
        key += f"|{epilogue}"
    return key


def matmul_q_key(m: int, n: int, k: int, dtype, backend,
                 epilogue: str = "none") -> str:
    """Int8-weight GEMM winners (kernels.ops.matmul_q). `dtype` is the
    ACTIVATION dtype — the weight is int8 by definition of the op. A
    Policy's quant field is normalised to "int8" before tagging so an
    explicit ops.matmul_q call and a quant-policy-routed dense_q call
    share one entry population; the int8-cost-model tiles must never be
    served to the full-width kernel (and vice versa), which the op
    prefix plus the fingerprint's _int8 suffix both enforce."""
    if getattr(backend, "quant", None) == "off":
        backend = backend.replace(quant="int8")
    key = (f"matmul_q|{m}x{n}x{k}|{np.dtype(dtype).name}|"
           f"{_backend_tag(backend)}")
    if epilogue not in (None, "none"):
        key += f"|{epilogue}"
    return key


def gated_key(m: int, n: int, k: int, dtype, backend) -> str:
    """The dual-GEMM SwiGLU kernel: (m, k) x 2*(k, n) -> (m, n)."""
    return f"gated|{m}x{n}x{k}|{np.dtype(dtype).name}|{_backend_tag(backend)}"


def flash_key(tq: int, tk: int, d: int, dtype, backend) -> str:
    return f"flash|{tq}x{tk}xd{d}|{np.dtype(dtype).name}|{_backend_tag(backend)}"


def flash_decode_key(tk: int, hkv: int, d: int, dtype, backend) -> str:
    """The decode kernel is q_len=1 by construction and streams bk rows
    of every kv head per grid step, so its shape key is (cache depth, kv
    heads, head dim) — every slot depth shares one entry (pos streams as
    data, not a trace constant)."""
    return (f"flash_decode|{tk}xh{hkv}xd{d}|{np.dtype(dtype).name}|"
            f"{_backend_tag(backend)}")


def flash_decode_paged_key(page_size: int, d: int, dtype, backend) -> str:
    """The paged decode kernel's tile space is keyed by (page_size,
    head_dim), not cache depth: bk must divide the page (one pool page
    — or a sub-tile of it — per grid step), so the same winner serves
    every pool size and slot count. The op prefix keeps these entries
    disjoint from dense flash_decode winners."""
    return (f"flash_decode_paged|p{page_size}xd{d}|{np.dtype(dtype).name}|"
            f"{_backend_tag(backend)}")


def ssd_key(chunk: int, p: int, n: int, dtype, backend) -> str:
    """SSD winners are keyed by (model chunk, head dim P, state dim N):
    chunking is algebraically exact, so the execution tile (q, bp) is a
    pure perf knob and any sequence length padded to the same model
    chunk shares one entry — L is deliberately absent from the key,
    like pos in flash_decode's."""
    return (f"ssd|Q{chunk}xP{p}xN{n}|{np.dtype(dtype).name}|"
            f"{_backend_tag(backend)}")


def flash_bwd_key(tq: int, tk: int, d: int, dtype, backend) -> str:
    """Backward winners get their own population: the two-sweep bwd
    kernel's working set (dK/dV accumulators + q/do/lse/delta streams)
    shifts the optimum away from the forward's."""
    return (f"flash_bwd|{tq}x{tk}xd{d}|{np.dtype(dtype).name}|"
            f"{_backend_tag(backend)}")


class TuningCache:
    """In-memory view of one fingerprint's entries, backed by the JSON
    file. `save()` is read-modify-write so caches for other fingerprints
    sharing the file survive."""

    def __init__(self, path: str | None = None,
                 fingerprint: str | None = None):
        self.path = path or default_cache_path()
        self.fingerprint = fingerprint or hw.fingerprint()
        self._entries: dict[str, dict] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    # --- persistence -----------------------------------------------------
    def load(self) -> "TuningCache":
        with self._lock:
            doc = self._read_file()
            if self._newer_format(doc):
                self._entries = {}    # unreadable to us; lookups miss
            else:
                self._entries = dict(
                    doc.get("caches", {}).get(self.fingerprint, {}))
        return self

    def save(self) -> str:
        with self._lock:
            doc = self._read_file()
            if self._newer_format(doc):
                raise RuntimeError(
                    f"{self.path} was written by a newer cache format "
                    f"(version {doc['version']} > {CACHE_VERSION}); refusing "
                    "to overwrite it — set REPRO_TUNING_CACHE to a fresh path")
            doc["version"] = CACHE_VERSION
            doc.setdefault("caches", {}).setdefault(
                self.fingerprint, {}).update(self._entries)
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            tmp = self.path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        return self.path

    @staticmethod
    def _newer_format(doc: dict) -> bool:
        return doc.get("version", CACHE_VERSION) > CACHE_VERSION

    def _read_file(self) -> dict:
        try:
            with open(self.path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            return {}
        return doc if isinstance(doc, dict) else {}

    # --- raw access ------------------------------------------------------
    def __len__(self) -> int:
        return len(self._entries)

    @property
    def entries(self) -> dict[str, dict]:
        return dict(self._entries)

    def get(self, key: str) -> Optional[dict]:
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
        else:
            self.hits += 1
        return entry

    def put(self, key: str, entry: dict) -> None:
        self._entries[key] = dict(entry)

    # --- typed accessors -------------------------------------------------
    def get_matmul(self, m: int, n: int, k: int, dtype, backend,
                   epilogue: str = "none") -> Optional[BlockConfig]:
        e = self.get(matmul_key(m, n, k, dtype, backend, epilogue))
        if e is None:
            return None
        return BlockConfig(bm=int(e["bm"]), bn=int(e["bn"]), bk=int(e["bk"]))

    def put_matmul(self, m: int, n: int, k: int, dtype, backend,
                   cfg: BlockConfig, *, epilogue: str = "none",
                   **meta: Any) -> str:
        key = matmul_key(m, n, k, dtype, backend, epilogue)
        self.put(key, {"bm": cfg.bm, "bn": cfg.bn, "bk": cfg.bk,
                       "tuned_at": _now(), **meta})
        return key

    def get_matmul_q(self, m: int, n: int, k: int, dtype, backend,
                     epilogue: str = "none") -> Optional[BlockConfig]:
        e = self.get(matmul_q_key(m, n, k, dtype, backend, epilogue))
        if e is None:
            return None
        return BlockConfig(bm=int(e["bm"]), bn=int(e["bn"]), bk=int(e["bk"]))

    def put_matmul_q(self, m: int, n: int, k: int, dtype, backend,
                     cfg: BlockConfig, *, epilogue: str = "none",
                     **meta: Any) -> str:
        key = matmul_q_key(m, n, k, dtype, backend, epilogue)
        self.put(key, {"bm": cfg.bm, "bn": cfg.bn, "bk": cfg.bk,
                       "tuned_at": _now(), **meta})
        return key

    def get_gated(self, m: int, n: int, k: int, dtype,
                  backend) -> Optional[BlockConfig]:
        e = self.get(gated_key(m, n, k, dtype, backend))
        if e is None:
            return None
        return BlockConfig(bm=int(e["bm"]), bn=int(e["bn"]), bk=int(e["bk"]))

    def put_gated(self, m: int, n: int, k: int, dtype, backend,
                  cfg: BlockConfig, **meta: Any) -> str:
        key = gated_key(m, n, k, dtype, backend)
        self.put(key, {"bm": cfg.bm, "bn": cfg.bn, "bk": cfg.bk,
                       "tuned_at": _now(), **meta})
        return key

    def get_flash(self, tq: int, tk: int, d: int, dtype,
                  backend) -> Optional[FlashBlockConfig]:
        e = self.get(flash_key(tq, tk, d, dtype, backend))
        if e is None:
            return None
        return FlashBlockConfig(bq=int(e["bq"]), bk=int(e["bk"]))

    def put_flash(self, tq: int, tk: int, d: int, dtype, backend,
                  cfg: FlashBlockConfig, **meta: Any) -> str:
        key = flash_key(tq, tk, d, dtype, backend)
        self.put(key, {"bq": cfg.bq, "bk": cfg.bk, "tuned_at": _now(), **meta})
        return key

    def get_flash_decode(self, tk: int, hkv: int, d: int, dtype,
                         backend) -> Optional[FlashBlockConfig]:
        e = self.get(flash_decode_key(tk, hkv, d, dtype, backend))
        if e is None:
            return None
        return FlashBlockConfig(bq=1, bk=int(e["bk"]))

    def put_flash_decode(self, tk: int, hkv: int, d: int, dtype, backend,
                         cfg: FlashBlockConfig, **meta: Any) -> str:
        key = flash_decode_key(tk, hkv, d, dtype, backend)
        self.put(key, {"bk": cfg.bk, "tuned_at": _now(), **meta})
        return key

    def get_flash_decode_paged(self, page_size: int, d: int, dtype,
                               backend) -> Optional[FlashBlockConfig]:
        e = self.get(flash_decode_paged_key(page_size, d, dtype, backend))
        if e is None:
            return None
        return FlashBlockConfig(bq=1, bk=int(e["bk"]))

    def put_flash_decode_paged(self, page_size: int, d: int, dtype, backend,
                               cfg: FlashBlockConfig, **meta: Any) -> str:
        key = flash_decode_paged_key(page_size, d, dtype, backend)
        self.put(key, {"bk": cfg.bk, "tuned_at": _now(), **meta})
        return key

    def get_ssd(self, chunk: int, p: int, n: int, dtype,
                backend) -> Optional[SSDBlockConfig]:
        e = self.get(ssd_key(chunk, p, n, dtype, backend))
        if e is None:
            return None
        return SSDBlockConfig(q=int(e["q"]), bp=int(e["bp"]))

    def put_ssd(self, chunk: int, p: int, n: int, dtype, backend,
                cfg: SSDBlockConfig, **meta: Any) -> str:
        key = ssd_key(chunk, p, n, dtype, backend)
        self.put(key, {"q": cfg.q, "bp": cfg.bp, "tuned_at": _now(), **meta})
        return key

    def get_flash_bwd(self, tq: int, tk: int, d: int, dtype,
                      backend) -> Optional[FlashBlockConfig]:
        e = self.get(flash_bwd_key(tq, tk, d, dtype, backend))
        if e is None:
            return None
        return FlashBlockConfig(bq=int(e["bq"]), bk=int(e["bk"]))

    def put_flash_bwd(self, tq: int, tk: int, d: int, dtype, backend,
                      cfg: FlashBlockConfig, **meta: Any) -> str:
        key = flash_bwd_key(tq, tk, d, dtype, backend)
        self.put(key, {"bq": cfg.bq, "bk": cfg.bk, "tuned_at": _now(), **meta})
        return key


def _now() -> str:
    return datetime.datetime.now().isoformat(timespec="seconds")


# --- process-global cache (what the `tuned` backend consults) ------------
_global: TuningCache | None = None
_global_lock = threading.Lock()


def get_cache(refresh: bool = False) -> TuningCache:
    """The shared cache instance, loaded lazily from default_cache_path().
    Re-resolved if REPRO_TUNING_CACHE changed since the last call, so
    tests and multi-experiment drivers can repoint it."""
    global _global
    with _global_lock:
        if _global is None or refresh or _global.path != default_cache_path():
            _global = TuningCache().load()
        return _global


def set_cache(cache: TuningCache | None) -> None:
    global _global
    with _global_lock:
        _global = cache


def reset_cache() -> None:
    set_cache(None)
