"""DoRA adapter configuration (paper §1, §4, App. B).

The config mirrors the paper's runtime knobs:
  - rank / alpha / rsLoRA scaling (``s`` appears in all three factored-norm
    terms, paper §7),
  - three-tier dispatch controls (mode, crossover thresholds),
  - norm implementation selector (factored vs. the two baselines the paper
    benchmarks against: dense ``B@A`` and PEFT's identity-matrix pattern),
  - chunk budget for the fp32 norm accumulation (paper default 256 MB),
  - ``save_inner`` — Tier-1 dual-output that saves ``inner = s*lora + base``
    for the magnitude gradient (skipped when the magnitude is frozen).
"""
from __future__ import annotations

import dataclasses
import math
import os


def _env_flag(name: str) -> str | None:
    v = os.environ.get(name)
    return v if v not in (None, "") else None


def shrink_block_rows(block_m: int, rows: int | None) -> int:
    """Decode-aware row-tile shrink: the block never exceeds the
    sublane-rounded row count, so small-M grids pad to the next multiple
    of 8 rows instead of a full tile. The ONE source of this rule — the
    config resolver, the shard-local kernel wrapper
    (``kernels.dora_compose.local_block_shape``) and the bench bytes
    model all derive their block_m through it."""
    if rows is None:
        return block_m
    return min(block_m, max(8, (rows + 7) // 8 * 8))


# Tier names (dispatch-table keys) → config modes. "tpu"/"pallas"/"fused"
# all mean the compiled-kernel path, which only a TPU host can run.
_TIER_ALIASES = {"tpu": "fused", "pallas": "fused", "fused": "fused",
                 "interpret": "interpret", "eager": "eager"}


def _normalize_tier(tier: str) -> str | None:
    return _TIER_ALIASES.get(tier.strip().lower())


@dataclasses.dataclass(frozen=True)
class DoRAConfig:
    """Configuration for DoRA adaptation of a linear layer family."""

    rank: int = 384
    alpha: float = 192.0
    rslora: bool = True

    # --- dispatch (paper §4, Table 2) ---
    # "auto": pallas on TPU above crossover, eager otherwise.
    # "fused": force pallas kernels (compiled for TPU).
    # "interpret": force pallas kernels in interpret mode (CPU validation).
    # "eager": force the pure-jnp Tier-3 path.
    mode: str = "auto"
    # Forced kernel tier ("tpu" | "interpret" | "eager"); overrides ``mode``
    # when set. The REPRO_FORCE_TIER env var overrides both, so any tier is
    # exercisable on any host without touching config plumbing.
    force_tier: str | None = None
    # Crossover below which launch latency dominates (paper §4: d_out >= 2048
    # and rows * d_out >= 2048 * 6144).
    min_fused_dout: int = 2048
    min_fused_elems: int = 2048 * 6144

    # --- norm (paper §2) ---
    # "factored" (ours) | "dense_ba" | "peft_eye" (baselines, §5.3 / §1).
    norm_impl: str = "factored"
    norm_chunk_mb: int | None = 256
    # Beyond-paper: precompute ||W||^2_row once (paper §2.3 "future work").
    cache_base_norm: bool = False

    # --- compose (paper §3) ---
    save_inner: bool = True
    magnitude_trainable: bool = True
    dropout: float = 0.0
    # Matmul-fused compose (beyond-paper, one fusion deeper): compute the
    # LoRA up-projection h@Bᵀ inside the compose kernel so y_lora is never
    # written to HBM. Only taken on the fused backends when the (128-padded)
    # rank stays below the crossover — above it the per-row-tile re-reads
    # of B exceed the y_lora write+read the fusion saves (B traffic ≈
    # (M/block_m)·d_out·r vs 2·M·d_out, i.e. profitable while
    # r ≲ 2·block_m). ``mm_fused_max_rank=None`` derives exactly that
    # 2·block_m bound from the bytes model at the CONFIGURED matmul-fused
    # block rows (``mm_block_rows``, falling back to ``block_rows``), so
    # tuning either knob re-calibrates the guard; set an int to pin it.
    compose_matmul_fused: bool = True
    mm_fused_max_rank: int | None = None

    # --- kernel block shapes (perf-tunable; see EXPERIMENTS.md §Perf) ---
    block_rows: int = 256
    block_cols: int = 1024
    # block_m of the matmul-fused compose grid; None → ``block_rows``.
    # Decode-shaped call sites (rows « block_rows) additionally shrink the
    # grid to the sublane-rounded row count via ``resolve_mm_block_rows``
    # so a 2-row decode batch is padded to 8 kernel rows, not 256.
    mm_block_rows: int | None = None
    norm_block_rows: int = 256
    norm_block_k: int = 512

    def __post_init__(self):
        if self.rank <= 0:
            raise ValueError(f"rank must be positive, got {self.rank}")
        if self.mode not in ("auto", "fused", "interpret", "eager"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if (self.force_tier is not None
                and _normalize_tier(self.force_tier) is None):
            raise ValueError(
                f"unknown force_tier {self.force_tier!r} (expected one of "
                f"'tpu'/'fused', 'interpret', 'eager')")
        if self.norm_impl not in ("factored", "dense_ba", "peft_eye"):
            raise ValueError(f"unknown norm_impl {self.norm_impl!r}")
        if self.mm_block_rows is not None and self.mm_block_rows <= 0:
            raise ValueError(
                f"mm_block_rows must be positive, got {self.mm_block_rows}")
        if self.dropout != 0.0:
            raise NotImplementedError(
                "dropout routes to the chunked eager path (paper App. B); "
                "only p=0 is wired in this repro")

    @property
    def scaling(self) -> float:
        """LoRA scaling s: alpha/rank, or alpha/sqrt(rank) under rsLoRA."""
        if self.rslora:
            return self.alpha / math.sqrt(self.rank)
        return self.alpha / self.rank

    def resolve_mode(self) -> str:
        """Apply the env-var overrides (paper App. B + forced tier).

        Precedence: REPRO_DORA_FUSED=0 kill switch > REPRO_FORCE_TIER >
        REPRO_DORA_MODE > ``force_tier`` config field > ``mode``.
        """
        if _env_flag("REPRO_DORA_FUSED") == "0":
            return "eager"
        tier = _env_flag("REPRO_FORCE_TIER")
        if tier is not None:
            mode = _normalize_tier(tier)
            if mode is None:
                raise ValueError(
                    f"REPRO_FORCE_TIER={tier!r} is not a known tier "
                    f"(expected 'tpu'/'fused', 'interpret', or 'eager')")
            return mode
        forced = _env_flag("REPRO_DORA_MODE")
        if forced is not None:
            mode = forced.strip().lower()
            if mode != "auto":
                mode = _normalize_tier(mode)
            if mode is None:
                raise ValueError(
                    f"REPRO_DORA_MODE={forced!r} is not a known mode "
                    f"(expected 'auto', 'fused'/'tpu', 'interpret', or "
                    f"'eager')")
            return mode
        if self.force_tier is not None:
            return _normalize_tier(self.force_tier)
        return self.mode

    def resolve_mm_block_rows(self, rows: int | None = None) -> int:
        """block_m of the matmul-fused compose grid.

        ``rows`` (the call site's flattened row count, when known) shrinks
        the grid for decode-shaped shapes: the block never exceeds the
        sublane-rounded row count, so small-M calls pad to the next
        multiple of 8 rows instead of a full ``block_rows`` tile.
        """
        bm = self.mm_block_rows if self.mm_block_rows is not None \
            else self.block_rows
        return shrink_block_rows(bm, rows)

    def resolve_mm_fused_max_rank(self, rows: int | None = None) -> int:
        """Rank crossover for the matmul-fused compose: explicit override
        or the bytes-model bound 2·block_m at the configured matmul-fused
        block rows (see the ``compose_matmul_fused`` field comment).
        ``rows`` prices the bound at the block the call site actually
        executes: decode-shaped calls shrink the grid, which shrinks the
        profitable rank range with it (the B re-reads stop amortizing) —
        the committed BENCH_compose.json decode row records exactly that
        regression."""
        if self.mm_fused_max_rank is not None:
            return self.mm_fused_max_rank
        return 2 * self.resolve_mm_block_rows(rows)

    def resolve_chunk_mb(self) -> int | None:
        env = _env_flag("REPRO_DORA_NORM_CHUNK_MB")
        if env is not None:
            v = int(env)
            return None if v <= 0 else v
        return self.norm_chunk_mb
