"""StepPlanner — cost-model-driven per-step packing decisions.

Each ragged mixed step packs decode rows (one token each, always first)
and then prompt chunks under the compiled ``token_budget``.  The shapes
of that step are deployment config; the *data* — how many prompt tokens
each chunk row contributes — is the scheduler's per-step choice.  The
planner makes that choice from predicted step wall: analytic bytes from
``StepCostModel`` × the steplog's rolling Σwall/Σbytes fit.

Planning modes:

  * static (fifo policy, no ITL SLO, or an uncalibrated fit): the
    chunk cap is the configured ``prefill_chunk`` — packing is
    byte-identical to the pre-sched engine.  The planner still
    PREDICTS the step wall so every record carries
    ``predicted_wall_s`` and the predicted-vs-measured error is
    reported for fifo and slack runs alike.
  * dynamic (slack policy with ``slo_itl_s``): when decode rows share
    the step with prompt chunks, the cap is halved until the predicted
    step wall fits the ITL budget (floor 1 — prefill always makes
    progress, so a tight SLO degrades prefill pace, never livelocks
    it).  Decode packing is untouched: every active row always gets
    its token.

Nothing here changes a shape: the executable key is independent of the
cap, so the one-executable / zero-recompile invariant is preserved by
construction.  The planner holds no locks — it runs on the stepping
thread under the engine's step lock and reads calibration from the
shared ``StepLog`` (which has its own lock, an edge already in the
lock-graph baseline).
"""
from __future__ import annotations

from typing import List, Optional

# minimum clean decode records before a fit is trusted for planning or
# admission predictions; below this everything degrades to static FIFO
MIN_FIT_SAMPLES = 8


class StepCalibration:
    """Read-only view of the steplog's rolling fits at one instant.

    ``scale_s_per_byte`` converts an analytic bytes estimate into
    predicted step wall; ``decode_step_s`` is the mean clean decode
    step wall (one emitted token per active row per step);
    ``prefill_s_per_token`` is Σwall/Σtokens over recent
    prefill-carrying steps."""

    __slots__ = ("scale_s_per_byte", "decode_step_s",
                 "prefill_s_per_token", "n_decode", "n_prefill")

    def __init__(self, scale_s_per_byte: Optional[float] = None,
                 decode_step_s: Optional[float] = None,
                 prefill_s_per_token: Optional[float] = None,
                 n_decode: int = 0, n_prefill: int = 0):
        self.scale_s_per_byte = scale_s_per_byte
        self.decode_step_s = decode_step_s
        self.prefill_s_per_token = prefill_s_per_token
        self.n_decode = int(n_decode)
        self.n_prefill = int(n_prefill)

    @property
    def fit_ready(self) -> bool:
        """Enough decode samples to trust bytes→wall predictions."""
        return (self.n_decode >= MIN_FIT_SAMPLES
                and (self.scale_s_per_byte or 0.0) > 0.0)

    @property
    def admission_ready(self) -> bool:
        """Enough samples to predict a queued request's completion."""
        return (self.fit_ready
                and self.n_prefill >= 1
                and (self.prefill_s_per_token or 0.0) > 0.0
                and (self.decode_step_s or 0.0) > 0.0)

    def as_dict(self) -> dict:
        return {"scale_s_per_byte": self.scale_s_per_byte,
                "decode_step_s": self.decode_step_s,
                "prefill_s_per_token": self.prefill_s_per_token,
                "n_decode": self.n_decode,
                "n_prefill": self.n_prefill,
                "fit_ready": self.fit_ready,
                "admission_ready": self.admission_ready}


class StepPlan:
    """One step's packing decision."""

    __slots__ = ("chunk_cap", "planned_tokens", "predicted_wall_s",
                 "limited")

    def __init__(self, chunk_cap: int, planned_tokens: int,
                 predicted_wall_s: float, limited: bool):
        self.chunk_cap = int(chunk_cap)          # per-row prompt cap
        self.planned_tokens = int(planned_tokens)  # budget chosen to fill
        self.predicted_wall_s = float(predicted_wall_s)
        self.limited = bool(limited)             # cap < static chunk


class StepPlanner:
    """Chooses each step's prompt-chunk cap and predicts its wall.

    Constructed by EngineCore next to the ``StepCostModel``; ``plan()``
    is called once per mixed step (under the step lock) and
    ``predict_wall()`` once more with the step's final bytes estimate
    so the record's prediction prices the composition actually packed.
    """

    def __init__(self, cost_model, steplog, *, max_batch: int,
                 token_budget: int, prefill_chunk: int,
                 slo_itl_s: Optional[float] = None,
                 dynamic: bool = False, refresh_every: int = 16):
        self._cost_model = cost_model
        self._steplog = steplog
        self._max_batch = int(max_batch)
        self._token_budget = int(token_budget)
        self._prefill_chunk = int(prefill_chunk)
        self._slo_itl_s = slo_itl_s
        self._dynamic = bool(dynamic)
        self._refresh_every = max(1, int(refresh_every))
        self._plans = 0
        self._limited = 0
        self._cal = StepCalibration()
        self._since_refresh = self._refresh_every   # refresh on first use

    # -------------------------------------------------------- calibration
    def calibration(self, refresh: bool = False) -> StepCalibration:
        """The current calibration view; re-read from the steplog every
        ``refresh_every`` plans (or immediately with ``refresh=True``)."""
        if refresh or self._since_refresh >= self._refresh_every:
            c = self._steplog.calibration()
            self._cal = StepCalibration(
                scale_s_per_byte=c.get("scale_s_per_byte"),
                decode_step_s=c.get("decode_step_s"),
                prefill_s_per_token=c.get("prefill_s_per_token"),
                n_decode=c.get("n_decode", 0),
                n_prefill=c.get("n_prefill", 0))
            self._since_refresh = 0
        return self._cal

    def predict_wall(self, bytes_est: float) -> float:
        """Predicted wall for a step that moves ``bytes_est`` analytic
        bytes; 0.0 while the fit is cold (recorded as "no prediction")."""
        cal = self._cal
        if not cal.fit_ready or bytes_est <= 0.0:
            return 0.0
        return float(bytes_est) * float(cal.scale_s_per_byte)

    # ----------------------------------------------------------- planning
    def _simulate(self, cap: int, n_decode: int,
                  pending: List[int], pages: int, key):
        """Pack ``pending`` prompt rows at per-row cap ``cap`` exactly
        the way the mixed step does, and price the composition."""
        budget = self._token_budget - n_decode
        chunk_tokens = 0
        chunk_rows = 0
        for p in pending:
            n = min(cap, budget - chunk_tokens, int(p))
            if n <= 0:
                continue
            chunk_tokens += n
            chunk_rows += 1
        tokens = n_decode + chunk_tokens
        rows = n_decode + chunk_rows
        kind = ("mixed" if chunk_tokens and n_decode else
                ("prefill" if chunk_tokens else "decode"))
        bts, _, _ = self._cost_model.estimate(
            kind, key, rows=max(rows, 1), max_rows=self._max_batch,
            pages_touched=pages, tokens=tokens)
        return tokens, self.predict_wall(bts)

    def plan(self, *, n_decode: int, pending: List[int], pages: int,
             key=None) -> StepPlan:
        """Choose this step's prompt-chunk cap.  ``pending`` holds the
        pending-prompt token counts of the chunk rows, ``pages`` the
        resident KV pages the step will run against."""
        self._plans += 1
        self._since_refresh += 1
        cal = self.calibration()
        cap = self._prefill_chunk
        tokens, predicted = self._simulate(cap, n_decode, pending,
                                           pages, key)
        if (not self._dynamic or self._slo_itl_s is None
                or not cal.fit_ready or not pending or n_decode == 0):
            # static plan: packing byte-identical to the pre-sched
            # engine (fifo compat), prediction still recorded
            return StepPlan(cap, tokens, predicted, limited=False)
        while cap > 1 and predicted > self._slo_itl_s:
            cap //= 2
            tokens, predicted = self._simulate(cap, n_decode, pending,
                                               pages, key)
        limited = cap < self._prefill_chunk
        if limited:
            self._limited += 1
        return StepPlan(cap, tokens, predicted, limited=limited)

    # ----------------------------------------------------------- metrics
    def snapshot(self) -> dict:
        """The ``sched.planner`` section of the metrics snapshot."""
        out = {"plans": self._plans,
               "chunk_limited_steps": self._limited,
               "dynamic": self._dynamic,
               "slo_itl_s": self._slo_itl_s,
               "token_budget": self._token_budget,
               "prefill_chunk": self._prefill_chunk}
        out["calibration"] = self._cal.as_dict()
        return out
