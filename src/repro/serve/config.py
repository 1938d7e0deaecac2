"""EngineConfig: every serving knob in one validated dataclass.

Four PRs of engine growth piled ten interdependent kwargs onto
``Engine.__init__`` and scattered their cross-field and family validation
through the constructor.  This module is the single source of truth for
both: the knobs live in one frozen dataclass, the field-level checks run in
``__post_init__``, and the family-dependent rules (which families are
servable, which can page, which need paging for the prefix cache) run in
:meth:`EngineConfig.validate` against the substrate capability sets
declared by ``repro.serve.backend``.

CLI integration is single-sourced too: :meth:`EngineConfig.add_cli_args`
registers the argparse flags and :meth:`EngineConfig.from_args` builds the
config back out of the parsed namespace — both launch CLIs
(``repro.launch.serve`` and ``examples/serve_luna.py``) share them, so a
new knob is added in exactly one place.

Legacy ``Engine(cfg, params, max_batch=..., paged=..., ...)`` kwargs were
removed one release after the v2 API landed (as promised): the engine
constructor takes an :class:`EngineConfig` and nothing else.
"""
from __future__ import annotations

from dataclasses import dataclass, fields, replace

from repro.serve.sampling import SamplingConfig

#: engine-level decode quantization modes (EngineConfig.quant); model-level
#: modes (bf16/int8/luna_* — dynamic per-call quantization via QuantConfig)
#: stay on the model config and share the ``--quant`` CLI flag.  lut4 is
#: the affine grid; nf4/nf4p evaluate the NF4 codebook through the D&C
#: split with a per-code residual correction — full for nf4, pruned below
#: a magnitude threshold for nf4p (table capacity vs bounded accuracy).
ENGINE_QUANT_MODES = ("lut4", "nf4", "nf4p")

#: speculative-decoding draft proposers (EngineConfig.spec).  "ngram" is
#: prompt-lookup drafting (no extra weights: the longest context-suffix
#: n-gram is matched against earlier prompt+output text and its
#: continuation proposed); "self_lut" is self-speculation — the SAME model
#: runs its decode step over pruned-LUT ``nf4p`` weights as the cheap
#: drafter while full precision verifies (LoCalut's capacity-computation
#: tradeoff applied to serving).  See ``docs/speculative.md``.
ENGINE_SPEC_MODES = ("ngram", "self_lut")


@dataclass(frozen=True)
class EngineConfig:
    """All engine knobs; see the README "Serving engine" section.

    * ``max_batch`` / ``max_seq`` — slot count and per-slot token budget.
    * ``prefill_bucket`` — prompt lengths are padded up to multiples of
      this and prefilled one jit call per bucket.
    * ``paged`` / ``block_size`` / ``num_blocks`` — paged-block KV cache
      (attention families): per-request block reservation instead of full
      ``max_seq`` rows; ``num_blocks=None`` sizes the pool at
      dense-equivalent capacity plus the reserved garbage block.
    * ``prefill_chunk`` — admit prompts longer than this in N-token chunks
      interleaved with decode ticks.
    * ``prefix_cache`` / ``prefix_cache_nodes`` — radix-tree prompt-prefix
      reuse (attention families require ``paged=True``).
    * ``sampling`` / ``seed`` — token sampling mode and the engine PRNG
      seed (``sampling=None`` means greedy).
    * ``starvation_bound`` — scheduler aging threshold: a queued request
      passed over this many times gains one priority bucket (see
      ``repro.serve.engine.Scheduler``).
    * ``idle_backoff_s`` — background serve loop (``engine.start()``):
      how long the loop thread sleeps when there is no queued, staged, or
      active work before re-checking (a ``submit()``/``cancel()`` wakes it
      immediately, so this only bounds shutdown latency and idle spin).
    * ``quant`` — decode weight quantization: ``"lut4"`` freezes decode
      projections to 4-bit codes on the affine grid, ``"nf4"`` encodes
      against the non-affine NF4 codebook, whose table is the
      least-squares D&C split plus a per-code residual correction,
      ``"nf4p"`` prunes that residual below a magnitude threshold (smaller
      tables, bounded accuracy cost); every mode runs the one LUT GEMM.
      ``None`` keeps bf16 decode token-identical to prior releases.
      Prefill always runs full precision; see ``docs/quantization.md``.
    * ``trace`` / ``trace_buffer`` — request-lifecycle tracing
      (``repro.obs``): record clock-stamped span events into a ring
      buffer of ``trace_buffer`` events, exportable as Perfetto JSON,
      each engine phase span also annotated on the JAX profiler's host
      timeline as ``engine.<phase>``.  Off by default (a disabled tracer
      is a cheap early-return); see ``docs/observability.md``.
    * ``spec`` / ``spec_k`` — speculative decoding: each tick drafts up
      to ``spec_k`` tokens per active request (``"ngram"`` prompt-lookup
      or ``"self_lut"`` self-speculation over nf4p LUT weights), scores
      the whole window in ONE batched verify pass, emits the accepted
      prefix plus the verifier's correction, and rolls back the rest.
      Greedy-only (acceptance is pinned token-identical to
      non-speculative greedy); see ``docs/speculative.md``.
    """
    max_batch: int = 8
    max_seq: int = 256
    prefill_bucket: int = 16
    paged: bool = False
    block_size: int = 16
    num_blocks: int | None = None
    prefill_chunk: int | None = None
    prefix_cache: bool = False
    prefix_cache_nodes: int = 256
    sampling: SamplingConfig | None = None
    seed: int = 0
    starvation_bound: int = 8
    quant: str | None = None
    idle_backoff_s: float = 0.002
    trace: bool = False
    trace_buffer: int = 65536
    spec: str | None = None
    spec_k: int = 4

    def __post_init__(self):
        if self.quant is not None and self.quant not in ENGINE_QUANT_MODES:
            raise ValueError(
                f"quant must be one of {ENGINE_QUANT_MODES} or None, "
                f"got {self.quant!r}")
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_seq < 2:
            raise ValueError(f"max_seq must be >= 2 (one prompt token + one "
                             f"generated), got {self.max_seq}")
        if self.prefill_bucket < 1:
            raise ValueError(f"prefill_bucket must be >= 1, "
                             f"got {self.prefill_bucket}")
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, "
                             f"got {self.prefill_chunk}")
        if self.paged and self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, "
                             f"got {self.block_size}")
        if self.prefix_cache and self.prefix_cache_nodes < 1:
            raise ValueError(f"prefix_cache_nodes must be >= 1, "
                             f"got {self.prefix_cache_nodes}")
        if self.starvation_bound < 1:
            raise ValueError(f"starvation_bound must be >= 1, "
                             f"got {self.starvation_bound}")
        if self.idle_backoff_s < 0:
            raise ValueError(f"idle_backoff_s must be >= 0, "
                             f"got {self.idle_backoff_s}")
        if self.trace_buffer < 1:
            raise ValueError(f"trace_buffer must be >= 1, "
                             f"got {self.trace_buffer}")
        if self.spec is not None and self.spec not in ENGINE_SPEC_MODES:
            raise ValueError(
                f"spec must be one of {ENGINE_SPEC_MODES} or None, "
                f"got {self.spec!r}")
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if self.spec is not None and self.sampling is not None \
                and self.sampling.mode != "greedy":
            raise ValueError(
                "speculative decoding is greedy-only (acceptance is pinned "
                "token-identical to non-speculative greedy argmax); got "
                f"spec={self.spec!r} with sampling mode "
                f"{self.sampling.mode!r}")

    # --- family cross-validation ----------------------------------------
    def validate(self, family: str) -> None:
        """Every family-dependent rule, in one place (previously scattered
        through ``Engine.__init__``)."""
        from repro.serve.backend import PAGED_FAMILIES, SERVED_FAMILIES
        if family in ("encdec", "vlm"):
            raise ValueError(
                f"family {family!r} needs modality inputs the text-only "
                "engine does not carry")
        if family not in SERVED_FAMILIES:
            raise ValueError(
                f"family {family!r} is not servable by this engine "
                f"(supported: {SERVED_FAMILIES})")
        if self.paged and family not in PAGED_FAMILIES:
            raise ValueError(
                f"paged=True is not supported for family {family!r}: "
                "its cache is O(1) recurrent state per slot with no KV "
                f"leaves to page (paged families: {PAGED_FAMILIES})")
        if self.prefix_cache and family in PAGED_FAMILIES and not self.paged:
            raise ValueError(
                f"prefix_cache for family {family!r} shares its "
                "attention KV as copy-on-write paged blocks — construct "
                "with paged=True (the ssm family caches dense state "
                "snapshots and needs no paging)")

    # --- CLI binding ----------------------------------------------------
    @staticmethod
    def add_cli_args(ap) -> None:
        """Register the shared engine flags on an argparse parser."""
        ap.add_argument("--max-batch", type=int, default=None,
                        help="concurrent sequence slots")
        ap.add_argument("--max-seq", type=int, default=None,
                        help="per-slot token budget (prompt + generation)")
        ap.add_argument("--prefill-bucket", type=int, default=None,
                        help="prompt lengths are padded up to multiples of "
                             "this and prefilled one jit call per bucket")
        ap.add_argument("--paged", action="store_true",
                        help="paged-block KV cache: per-request block "
                             "reservation instead of full max-seq rows "
                             "(attention families)")
        ap.add_argument("--block-size", type=int, default=None,
                        help="tokens per KV block in --paged mode")
        ap.add_argument("--num-blocks", type=int, default=None,
                        help="pool size in blocks (default: dense-equivalent "
                             "capacity + the reserved garbage block)")
        ap.add_argument("--prefill-chunk", type=int, default=None,
                        help="admit prompts longer than this in N-token "
                             "chunks interleaved with decode ticks")
        ap.add_argument("--prefix-cache", action="store_true",
                        help="radix-tree prompt-prefix sharing: warm "
                             "admissions reuse cached KV blocks (attention, "
                             "needs --paged) or recurrent state snapshots "
                             "(ssm) and prefill only the uncached tail")
        ap.add_argument("--prefix-cache-nodes", type=int, default=None,
                        help="LRU budget for cached prefix boundaries")
        ap.add_argument("--idle-backoff-s", type=float, default=None,
                        help="background serve loop: idle sleep between "
                             "re-checks when no work is pending")
        ap.add_argument("--trace", action="store_true",
                        help="record request-lifecycle + engine-phase trace "
                             "events (ring-buffered; export with "
                             "--trace-out)")
        ap.add_argument("--trace-buffer", type=int, default=None,
                        help="trace ring-buffer capacity in events "
                             "(oldest dropped on overflow)")
        ap.add_argument("--trace-out", default=None, metavar="PATH",
                        help="write the trace as Perfetto/Chrome "
                             "trace_event JSON on exit (implies --trace); "
                             "open at https://ui.perfetto.dev")
        ap.add_argument("--metrics-port", type=int, default=None,
                        help="serve the metrics registry at "
                             "http://127.0.0.1:PORT/metrics (Prometheus "
                             "text exposition) from a background thread")
        ap.add_argument("--metrics-dump", default=None, metavar="PATH",
                        help="write the Prometheus text exposition to PATH "
                             "on exit")
        ap.add_argument("--spec", default=None,
                        choices=list(ENGINE_SPEC_MODES),
                        help="speculative decoding draft proposer: 'ngram' "
                             "(prompt-lookup, no extra weights) or "
                             "'self_lut' (self-speculation: the same model "
                             "over pruned nf4p LUT weights drafts, full "
                             "precision verifies); greedy-only")
        ap.add_argument("--spec-k", type=int, default=None,
                        help="max draft tokens per request per tick "
                             "(speculation window = spec_k + 1)")
        ap.add_argument("--sampling", default="greedy",
                        choices=["greedy", "temperature", "top_k"])
        ap.add_argument("--temperature", type=float, default=1.0)
        ap.add_argument("--top-k", type=int, default=40)
        ap.add_argument("--seed", type=int, default=0)
        ap.add_argument("--quant", default=None,
                        help="weight quantization: 'lut4' (4-bit affine "
                             "decode weights through the LUT gemm), 'nf4' "
                             "(non-affine NF4 codebook, D&C + residual "
                             "correction) or 'nf4p' (pruned residual sub-"
                             "table) quantize the DECODE hot path at "
                             "engine construction; any other value (bf16, "
                             "int8, int4_dequant, lut_nf4, luna_*) is a "
                             "model-level mode applied dynamically to "
                             "every projection")

    @classmethod
    def from_args(cls, args, **overrides) -> "EngineConfig":
        """Build a config from an argparse namespace produced by
        :meth:`add_cli_args`.  ``overrides`` win over CLI values (a CLI may
        pin e.g. ``max_batch`` instead of exposing the flag); flags the
        parser left at None fall back to the dataclass defaults.  The
        shared ``--quant`` flag reaches ``EngineConfig.quant`` only for
        engine-level modes — model-level spellings (bf16/luna_*/...) are
        the caller's to route into a ``QuantConfig`` and leave the engine
        field at None."""
        cfg = cls()
        vals = {}
        for f in fields(cls):
            if f.name in ("sampling", "quant"):
                continue
            v = getattr(args, f.name, None)
            if v is not None and v is not False:
                vals[f.name] = v
        q = getattr(args, "quant", None)
        if q in ENGINE_QUANT_MODES:
            vals["quant"] = q
        if getattr(args, "trace_out", None):
            vals["trace"] = True       # a trace sink implies recording
        mode = getattr(args, "sampling", "greedy")
        vals["sampling"] = SamplingConfig(
            mode=mode, temperature=getattr(args, "temperature", 1.0),
            top_k=getattr(args, "top_k", 0) if mode == "top_k" else 0)
        vals.update(overrides)
        return replace(cfg, **vals)
