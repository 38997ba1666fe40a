"""Speculative decoding: draft-model proposals, chunk-shaped verify,
deterministic rollback.

The paper's core throughput lever is chaining — overlapping dependent
functional units so the FPU never idles; the serving analogue is
draft-verify decoding.  A small draft LM autoregressively proposes ``k``
tokens per slot, then the target model scores all of them in ONE
chunk-shaped step (``LM.verify_chunk`` riding ``ops.flash_prefill_chunk``'s
runtime causal boundary), amortising the target's weight traffic over k
positions instead of one — memory-bound decode moves toward the original
Ara's multi-operand-per-cycle regime.

Two earlier PRs make the hard parts fall out:

  * **Verify is a prompt chunk.**  ``flash_prefill_chunk`` already attends
    row j at q-position ``start + j`` over exactly the keys ``flash_decode``
    at ``pos = start + j`` would — same blockwise online-softmax, same mask
    set — so chunk-path logits are bit-identical to decode-path logits and
    the verify pass is literally a replay of k sequential decode steps at
    chunk cost.
  * **Rollback has no PRNG state.**  Every draw's key folds only
    ``(request seed, absolute position)``, so the target's draw at each
    verify position (:func:`~repro.runtime.serving.sampling.verify_draws`,
    the *Gumbel replay*) equals the token non-speculative decode would have
    sampled there.  Acceptance is exact token match against those draws —
    greedy traffic short-circuits to argmax match — which makes the
    committed stream the target's own stream verbatim: speculation is a
    pure latency optimisation, bit-identical output for every
    (seed, temperature), including under preemption/recompute and donation.

Rollback itself is arena surgery by *not writing*: the verify chunk's
scattered K/V rows past the accepted prefix are dead (causal masking never
reads rows >= the committed position; the next round's chunk overwrites
them), so rejecting k - a proposals costs rewinding a host-side position
cursor.  The draft cache lives in a second slot-major arena sharing the
target's slot indices; prefill mirrors every target chunk into it and
preemption/recompute re-ingests both in lockstep, so the two caches always
agree on rows [0, pos).

Adaptive k: a per-engine EMA of the acceptance fraction walks ``k`` along a
power-of-two ladder — down toward 1 when recent acceptance is low (so
adversarial traffic never regresses below one committed token per target
step), up toward ``k_max`` when proposals keep landing.  The ladder bounds
the distinct verify-chunk shapes, so verify executables stay one-per-bucket
no matter how long the engine runs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding knobs (``EngineConfig.speculative``).

    ``draft``       the draft LM: a registry arch name (e.g.
                    ``"llama3_2_3b"``, built reduced) or an ``ArchConfig``;
                    must share the target's vocab
    ``k``           initial proposals per round (also the adaptive ladder's
                    starting rung)
    ``k_max``       adaptive ceiling (ladder rungs are powers of two in
                    [1, k_max], plus ``k`` itself)
    ``adaptive``    walk k with the acceptance EMA; False pins k
    ``low``/``high``acceptance-EMA thresholds: EMA < low steps k down,
                    EMA > high steps k up
    ``window``      rounds between adaptation decisions (anti-thrash)
    ``ema``         EMA decay toward history per round
    ``draft_seed``  PRNG seed for the draft model's parameter init (the
                    draft is a *stand-in* model here — production would
                    load trained draft weights; determinism of the output
                    stream never depends on the draft's quality, only the
                    acceptance rate does)
    """
    draft: Any
    k: int = 4
    k_max: int = 8
    adaptive: bool = True
    low: float = 0.4
    high: float = 0.85
    window: int = 8
    ema: float = 0.8
    draft_seed: int = 0

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"SpecConfig.k must be >= 1, got {self.k}")
        if self.k_max < self.k:
            raise ValueError(f"SpecConfig.k_max must be >= k={self.k}, "
                             f"got {self.k_max}")
        if not 0.0 <= self.low < self.high <= 1.0:
            raise ValueError(
                f"SpecConfig thresholds need 0 <= low < high <= 1, got "
                f"low={self.low} high={self.high}")
        if self.window < 1:
            raise ValueError(f"SpecConfig.window must be >= 1, "
                             f"got {self.window}")
        if not 0.0 < self.ema < 1.0:
            raise ValueError(f"SpecConfig.ema must be in (0, 1), "
                             f"got {self.ema}")

    def ladder(self) -> tuple[int, ...]:
        """The allowed k values: powers of two up to ``k_max`` plus the
        configured starting k.  Bounds the distinct verify-chunk shapes —
        the 'one executable per bucket' guarantee."""
        rungs = {self.k}
        r = 1
        while r <= self.k_max:
            rungs.add(r)
            r *= 2
        return tuple(sorted(rungs))


class SpecController:
    """Pairs a draft LM with the target and owns the host-side speculative
    state: the resolved draft model, the adaptive-k walk, and the
    acceptance bookkeeping.  The engine owns the device side (both arenas,
    the compiled draft/verify executables) and calls back here once per
    round; the controller is device-free and unit-testable without jax
    arrays.
    """

    #: families whose chunk-path logits are bit-identical to decode-path
    #: logits — the precondition for the determinism contract.  Recurrent
    #: families (ssm/hybrid) rewind state, not positions; MoE's dropless
    #: layer is per-row, but no test pins its verify against its decode.
    _OK_FAMILIES = ("dense",)

    def __init__(self, target_cfg, spec: SpecConfig):
        self.spec = spec
        self.draft_model, self.draft_cfg = self._resolve_draft(spec.draft)
        for role, cfg in (("target", target_cfg), ("draft", self.draft_cfg)):
            if cfg.family not in self._OK_FAMILIES:
                raise ValueError(
                    f"speculative decoding requires a family whose chunk "
                    f"logits replay decode bit-exactly "
                    f"({'/'.join(self._OK_FAMILIES)}); {role} family is "
                    f"{cfg.family!r}")
        if self.draft_cfg.vocab != target_cfg.vocab:
            raise ValueError(
                f"draft vocab {self.draft_cfg.vocab} != target vocab "
                f"{target_cfg.vocab}: acceptance compares token ids")
        self._ladder = spec.ladder()
        self.k = spec.k
        self._ema: Optional[float] = None
        self._since_adapt = 0
        self.stats = {"rounds": 0, "proposed": 0, "accepted": 0,
                      "resamples": 0, "k_changes": 0, "per_request": {}}

    #: draft-model memo: the resolved (model, cfg) per draft spec.  Engines
    #: built with the same draft share one model *instance*, so the jitted
    #: draft executables (keyed on the instance) compile once per process,
    #: not once per engine — benches and tests rebuild engines freely.
    _draft_memo: dict = {}

    @classmethod
    def _resolve_draft(cls, draft):
        """Registry name -> reduced bundle; ArchConfig -> built model."""
        from repro.models import registry
        try:
            hit = cls._draft_memo.get(draft)
        except TypeError:               # unhashable config: build fresh
            return registry.build_model(draft), draft
        if hit is not None:
            return hit
        if isinstance(draft, str):
            bundle = registry.build(draft, reduced=True)
            resolved = (bundle.model, bundle.cfg)
        else:
            resolved = (registry.build_model(draft), draft)
        cls._draft_memo[draft] = resolved
        return resolved

    # -- acceptance bookkeeping + adaptive k ---------------------------------
    @property
    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the target accepted so far."""
        return self.stats["accepted"] / max(self.stats["proposed"], 1)

    def observe_round(self, outcomes) -> None:
        """Record one round's per-slot outcomes — ``(uid, accepted,
        proposed)`` triples — then let the EMA walk k along the ladder.
        Called once per engine spec round."""
        if not outcomes:
            return
        self.stats["rounds"] += 1
        fracs = []
        for uid, accepted, proposed in outcomes:
            self.stats["accepted"] += accepted
            self.stats["proposed"] += proposed
            if accepted < proposed:
                self.stats["resamples"] += 1
            acc, prop = self.stats["per_request"].get(uid, (0, 0))
            self.stats["per_request"][uid] = (acc + accepted,
                                              prop + proposed)
            fracs.append(accepted / proposed)
        mean = sum(fracs) / len(fracs)
        self._ema = mean if self._ema is None else (
            self.spec.ema * self._ema + (1.0 - self.spec.ema) * mean)
        self._maybe_adapt()

    def _maybe_adapt(self) -> None:
        if not self.spec.adaptive:
            return
        self._since_adapt += 1
        if self._since_adapt < self.spec.window:
            return
        i = self._ladder.index(self.k)
        if self._ema < self.spec.low and i > 0:
            self.k = self._ladder[i - 1]
            self.stats["k_changes"] += 1
            self._since_adapt = 0
        elif self._ema > self.spec.high and i + 1 < len(self._ladder):
            self.k = self._ladder[i + 1]
            self.stats["k_changes"] += 1
            self._since_adapt = 0
