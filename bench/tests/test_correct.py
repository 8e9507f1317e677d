"""``correct`` at a size the CPU holds: a sound run passes, the control
(the reference in the nearest precision below the stated one) and each
fault the cell can have (an answer or a token altered where it is
produced) fail.  The device check is left out; the rest of a run is the
drivers' own code."""
import numpy as np
import pytest

from bench import common, social
from bench.ref_analysis import Reference, analysis_error, bf16
from bench.tests import tiny

BENCH = common.manifest()
ANALYSIS_CELLS = [w["name"] for w in BENCH["workloads"]
                  if common.config_of(BENCH, w)["driver"] == "analysis"]
# and a cell the manifest does not hold: the first one's configuration on
# four chips, as a sharded deployment would declare it
CELLS = ANALYSIS_CELLS + [(ANALYSIS_CELLS[0], 4)]


@pytest.mark.parametrize("cell", CELLS, ids=tiny.label)
def test_sound_analysis_run_is_correct(cell, request):
    if tiny.elsewhere(request, cell):
        return
    run = tiny.drive(cell)
    assert run.correct, [(c.name, c.value) for c in run.checks]
    assert run.attempted > 0 and run.failed == 0
    chips = run.cell["chips"]
    for leaf in run.extra["placement"]:
        assert leaf["devices"] == chips, leaf["leaf"]
        if chips > 1 and leaf["shape"] and (
                leaf["leaf"].startswith("tweets") or "blk_" in leaf["leaf"]):
            # the row, dst-block and doc-block partitions themselves
            assert leaf["split"], leaf["leaf"]
            assert leaf["shard_rows"] * chips == leaf["shape"][0], leaf


def _alter_answers(drv, cfg):
    init = drv.Cell.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        run_one = self.rt._analysis_exec

        def altered(req, keys=None):
            out, info = run_one(req, keys=keys)
            return out.at[0].add(1e-2 * abs(out).max() + 1.0), info
        self.rt._analysis_exec = altered
    drv.Cell.__init__ = patched


@pytest.mark.parametrize("cell", CELLS, ids=tiny.label)
def test_altered_answer_is_not_correct(cell, request):
    if tiny.elsewhere(request, cell):
        return
    run = tiny.drive(cell, patch=_alter_answers)
    assert not run.correct
    assert {c.name for c in run.checks if not c.ok} == {"score_err"}


def test_bfloat16_control_fails_the_analysis_limit():
    _, _, cfg, _ = tiny.cell_files("pulse-8m.mixed")
    drv = common.driver(cfg)
    data = social.build_social_data(np.random.default_rng(4),
                                    **dict(drv.TINY_DATA, doc_tokens=(7, 15)))
    ref, low = Reference(data), Reference(data, rounding=bf16)
    rng = np.random.default_rng(5)
    for _ in range(3):
        q = social.query_vector(rng.zipf(1.4, 6), data.vocab)
        want, kth, spare = ref.result(q)
        err, _ = analysis_error(low.result(q)[0], want, kth, spare)
        assert err > cfg["check"]["score_err"]


def test_sound_lm_run_is_correct():
    run = tiny.drive("qwen3-0.6b.chat", seconds=3.0)
    assert run.correct, [(c.name, c.value) for c in run.checks]
    assert run.attempted > 0 and run.failed == 0


def _alter_tokens(drv, cfg):
    from repro.serving import runtime
    init = runtime.AsyncServingRuntime.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        step = self._dstep

        def altered(p, c, t, i):
            logits, cache = step(p, c, t, i)
            # every decoded token moves one id up from the model's choice
            return np.roll(np.asarray(logits), 1, axis=-1), cache
        self._dstep = altered
    runtime.AsyncServingRuntime.__init__ = patched


def test_altered_token_is_not_correct():
    from repro.serving import runtime
    init = runtime.AsyncServingRuntime.__init__
    try:
        run = tiny.drive("qwen3-0.6b.chat", seconds=3.0, patch=_alter_tokens)
    finally:
        runtime.AsyncServingRuntime.__init__ = init
    assert not run.correct
    assert {c.name for c in run.checks if not c.ok} == {"logit_gap"}


def test_float8_control_fails_the_lm_limit():
    from bench import ref_lm
    run = tiny.drive("qwen3-0.6b.chat", seconds=3.0)
    cfg, params = run.config, run.extra["params"]
    gaps = [ref_lm.control_gap(cfg, params, r["prompt"], r["tokens"])
            for r in run.extra["sample"]]
    assert max(gaps) > cfg["check"]["logit_gap"]
