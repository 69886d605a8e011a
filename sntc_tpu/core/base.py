"""Estimator / Transformer / Pipeline — the user-facing capability surface.

Behavioral spec: Spark ML's pipeline abstractions (SURVEY.md §1 L1; upstream
``python/pyspark/ml/{base,pipeline}.py`` and
``mllib/.../org/apache/spark/ml/Pipeline.scala`` [U]):

  * ``Transformer.transform(frame) -> frame`` appends columns;
  * ``Estimator.fit(frame) -> Model`` learns and returns a fitted Transformer;
  * ``Pipeline`` chains stages: during ``fit``, transformers transform eagerly
    and estimators fit on the accumulated frame, producing a ``PipelineModel``
    of fitted stages (call-stack parity: SURVEY.md §3.1).

Unlike Spark there is no Py4J/JVM boundary (deleted per SURVEY.md §1 restack):
stages are plain Python objects whose numeric inner loops dispatch to JAX/XLA.
"""

from __future__ import annotations

import itertools
import time
from typing import Any, Dict, List, Optional

from sntc_tpu.core.frame import Frame
from sntc_tpu.core.params import NO_DEFAULT, Param, Params
from sntc_tpu.obs import module_of, set_gauge, span

_MODULE = module_of(__name__)
#: per-process count of ``Pipeline.fit`` / ``PipelineModel.transform``
#: calls: the ``run=`` attribute that tells one root span from the next
_RUNS = itertools.count(1)
#: whether this process has entered a ``Pipeline.fit`` yet (``_RUNS``
#: counts transforms too, so ``run=1`` does not say it)
_first_fit_claimed = False


def _stage_span(op: str, stage, index: int):
    """``stage.fit`` / ``stage.transform`` / ``stage.fit_transform``
    around one stage's call."""
    cls = type(stage)
    return span(op, stage=cls.__name__, index=index, module=module_of(cls))


class PipelineStage(Params):
    """Common base for Transformer and Estimator."""

    # the conventional input-column param names this base can discover;
    # stages reading columns through differently-named params MUST
    # override input_columns() so pipeline rewrites (sntc_tpu.fuse) and
    # the tuning prefix hoist see them
    _INPUT_COL_PARAMS = ("inputCol", "featuresCol", "inputCols")

    def input_columns(self) -> List[str]:
        """Column names this stage reads — at transform time for
        Transformers, at fit time for Estimators (unset params
        contribute nothing — an unset stage consumes nothing yet)."""
        out: List[str] = []
        for name in self._INPUT_COL_PARAMS:
            if not self.hasParam(name) or not self.isDefined(name):
                continue
            val = self.getOrDefault(name)
            if val is None:
                continue
            out.extend(val if isinstance(val, (list, tuple)) else [val])
        return out

    def save(self, path: str) -> str:
        """Persist this stage (SURVEY.md §5.4); see sntc_tpu.mlio."""
        from sntc_tpu.mlio import save_model

        return save_model(self, path)

    @classmethod
    def load(cls, path: str) -> "PipelineStage":
        from sntc_tpu.mlio import load_model

        obj = load_model(path)
        if not isinstance(obj, cls):
            raise TypeError(
                f"{path} holds a {type(obj).__name__}, not a {cls.__name__}"
            )
        return obj


class Transformer(PipelineStage):
    def transform(self, frame: Frame) -> Frame:
        raise NotImplementedError

    def transform_async(self, frame: Frame):
        """Dispatch this transform without blocking on device results.

        Returns a zero-arg ``finalize`` callable that materializes and
        returns the output Frame.  Device-backed models override this to
        dispatch their compute and defer host materialization, so a caller
        can overlap the NEXT batch's host work with this batch's device
        compute and transfer — the serving micro-batch pipeline ([B:11];
        JAX dispatch is asynchronous, only materialization blocks).  The
        default runs synchronously and is always correct.

        Thread contract (the pipelined engine relies on it): ``finalize``
        may be invoked from a DIFFERENT thread than the dispatching one —
        the overlapped retire stage materializes batch N on its delivery
        thread while the engine thread dispatches batch N+1 — and may be
        invoked MORE THAN ONCE (the engine's sink retry path re-invokes
        it per delivery attempt; the serving ``BatchPredictor`` memoizes,
        so engine deliveries materialize once, but a bare override must
        still tolerate re-invocation — re-materializing a jax.Array is
        fine).  Overrides must close over immutable per-call state only;
        mutating shared transformer state inside finalize is a data race.
        """
        out = self.transform(frame)
        return lambda: out

    def __call__(self, frame: Frame) -> Frame:
        return self.transform(frame)


class Estimator(PipelineStage):
    def fit(self, frame: Frame, params: Optional[Dict[str, Any]] = None) -> "Model":
        """Fit on ``frame``. ``params`` is a one-shot override map (Spark's
        ``fit(dataset, paramMap)`` convenience used by tuning)."""
        if params:
            return self.copy(params).fit(frame)
        return self._fit(frame)

    def _fit(self, frame: Frame) -> "Model":
        raise NotImplementedError

    def fit_transform(self, frame: Frame):
        """``(model, model.transform(frame))``: what ``Pipeline.fit`` needs
        of an estimator that feeds later stages.  An estimator that can
        make both from one pass over ``frame`` overrides this;
        ``Pipeline`` then opens one ``stage.fit_transform`` span for the
        call where it opens ``stage.fit`` and ``stage.transform`` for the
        two calls of this default."""
        model = self.fit(frame)
        return model, model.transform(frame)


class Evaluator(PipelineStage):
    """Metric computer over a predictions Frame (Spark's
    ``ml/evaluation/Evaluator`` [U]).  A Params stage like every other
    pipeline piece, so tuning results persist/restore their evaluator
    spec (``CrossValidatorModel.save`` round-trips it)."""

    def evaluate(self, frame: Frame) -> float:
        raise NotImplementedError

    def isLargerBetter(self) -> bool:
        return True


class Model(Transformer):
    """A fitted Transformer produced by ``Estimator.fit``."""


class Pipeline(Estimator):
    """Chain of stages; ``fit`` returns a :class:`PipelineModel`.

    Spark semantics (SURVEY.md §3.1): stages before the last estimator are
    applied in order — transformers transform the running frame eagerly, each
    estimator is fit on the running frame and its fitted model then transforms
    the frame for downstream stages (one ``fit_transform`` call where the
    estimator overrides it).
    """

    stages = Param("pipeline stages (Transformers and Estimators), applied in order")

    def __init__(self, stages: Optional[List[PipelineStage]] = None, **kwargs: Any):
        super().__init__(**kwargs)
        if stages is not None:
            self.set("stages", list(stages))

    def _fit(self, frame: Frame) -> "PipelineModel":
        stages = self.getStages()
        for stage in stages:
            if not isinstance(stage, (Transformer, Estimator)):
                raise TypeError(
                    f"pipeline stage {stage!r} is neither Transformer nor Estimator"
                )
        # Spark parity: only stages BEFORE the last estimator need to feed
        # transformed data downstream — the last estimator's model transform
        # over the training set would be discarded, so skip it.
        last_est = max(
            (i for i, s in enumerate(stages) if isinstance(s, Estimator)),
            default=-1,
        )
        global _first_fit_claimed
        first, _first_fit_claimed = not _first_fit_claimed, True
        fitted: List[Transformer] = []
        current = frame
        t0 = time.perf_counter()
        with span("pipeline.fit", stages=len(stages), run=next(_RUNS),
                  module=_MODULE):
            for i, stage in enumerate(stages):
                model = stage
                if (i < last_est and isinstance(stage, Estimator)
                        and type(stage).fit_transform
                        is not Estimator.fit_transform):
                    with _stage_span("stage.fit_transform", stage, i):
                        model, current = stage.fit_transform(current)
                else:
                    if isinstance(stage, Estimator):
                        with _stage_span("stage.fit", stage, i):
                            model = stage.fit(current)
                    if i < last_est:
                        with _stage_span("stage.transform", model, i):
                            current = model.transform(current)
                fitted.append(model)
        if first:
            set_gauge("sntc_pipeline_first_fit_seconds",
                      time.perf_counter() - t0)
        return PipelineModel(stages=fitted)


class PipelineModel(Model):
    """Fitted pipeline: applies each fitted stage's transform in order."""

    stages = Param("fitted pipeline stages (all Transformers)")

    def __init__(self, stages: Optional[List[Transformer]] = None, **kwargs: Any):
        super().__init__(**kwargs)
        if stages is not None:
            self.set("stages", list(stages))

    def transform(self, frame: Frame) -> Frame:
        current = frame
        stages = self.getStages()
        with span("pipeline.transform", stages=len(stages),
                  run=next(_RUNS), module=_MODULE):
            for i, stage in enumerate(stages):
                with _stage_span("stage.transform", stage, i):
                    current = stage.transform(current)
        return current

    def transform_async(self, frame: Frame):
        """Host stages before the last device-dispatching stage run now;
        that stage's dispatch is deferred to its own ``transform_async``
        (feature prep for batch i+1 overlaps batch i's device compute in a
        pipelined serve loop), and trailing host-only stages (e.g.
        ``IndexToString`` on the prediction) run inside finalize."""
        stages = self.getStages()
        if not stages:
            return lambda: frame
        split = len(stages) - 1
        for i in reversed(range(len(stages))):
            if (
                type(stages[i]).transform_async
                is not Transformer.transform_async
            ):
                split = i
                break
        current = frame
        for stage in stages[:split]:
            current = stage.transform(current)
        fin = stages[split].transform_async(current)
        tail = stages[split + 1:]
        if not tail:
            return fin

        def finalize():
            out = fin()
            for stage in tail:
                out = stage.transform(out)
            return out

        return finalize
