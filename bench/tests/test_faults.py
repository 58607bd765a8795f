"""A whole run on the CPU at a tiny size, past the harness's look for a
chip, with the timed path broken underneath: ``correct`` must come out
false for each fault a cell can have, and true with none."""

import json
import sys

import pytest

import run
import spec


@pytest.fixture
def tiny(monkeypatch, capsys):
    import jax

    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)
    config, traffic = spec.config, spec.traffic

    def small_config(bench, cell):
        cfg = config(bench, cell)
        cfg["graph"].update(rows=12, cols=12)
        cfg["index"]["z"] = 24
        cfg["service"]["engine"] = "dense_bf"  # the kernel interprets slowly
        return cfg

    def short_traffic(cell):
        mix = traffic(cell)
        mix.update(warmup_seconds=1.0, drain_seconds=8.0)
        return mix

    monkeypatch.setattr(spec, "config", small_config)
    monkeypatch.setattr(spec, "traffic", short_traffic)

    def go(*extra):
        monkeypatch.setattr(sys, "argv", [
            "run.py", "--workload", "col-s.saturate", "--seed",
            str(2**31 + 11), "--seconds", "3", "--trace", "0", *extra])
        run.main()
        got = capsys.readouterr()
        sys.stderr.write(got.err)  # shown with a failure
        return json.loads(got.out.strip().splitlines()[-1])

    return go


def _break_answers(monkeypatch, alter):
    """Wrap the scheduler's step that produces a finished query's answer."""
    from repro.dist.scheduler import QueryScheduler

    advance = QueryScheduler._advance

    def broken(self, tk, seg_lists):
        advance(self, tk, seg_lists)
        if tk.done:
            alter(tk)

    monkeypatch.setattr(QueryScheduler, "_advance", broken)


def test_sound_run_is_correct(tiny):
    out = tiny()
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["metrics"]["throughput_qps"]["value"] > 0
    assert list(out)[-1] == "checks"


def test_pairs_from_seed_runs_whole(tiny):
    # at this tiny size the program truncates some fresh pairs (PERF.md,
    # Open questions), so only the run's course is checked here
    out = tiny("--pairs-from-seed")
    assert out["attempted"] > 0
    assert set(out["checks"]) == {"bad_answers", "dist_gap"}
    assert list(out)[-1] == "checks"


def test_altered_answer_is_caught(tiny, monkeypatch):
    def alter(tk):
        if tk.result:
            d, p = tk.result[-1]
            tk.result[-1] = (d + 1.0, p)

    _break_answers(monkeypatch, alter)
    out = tiny()
    assert out["correct"] is False
    assert out["checks"]["dist_gap"]["value"] > 1e-3


def test_half_the_answers_left_out_is_caught(tiny, monkeypatch):
    from repro.service import KSPService

    tick = KSPService.tick
    seen = [0]

    def lossy(self):
        kept = []
        for t in tick(self):
            seen[0] += 1
            if seen[0] % 2:
                kept.append(t)
        return kept

    monkeypatch.setattr(KSPService, "tick", lossy)
    out = tiny()
    assert out["correct"] is False and out["failed"] > 0
