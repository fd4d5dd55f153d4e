"""The comparison that decides ``correct``, shown to fail: at tiny widths
on the CPU, (1) the float8 control fails a number that sound runs pass,
and (2) a run with its timed path broken underneath comes out not
correct, for each fault a serving cell can have: a served token altered
where a decode step or an admission produces it, and a decode step that
hands back its KV cache unchanged.  (Half a batch left out is a training
fault: a serving cell checks every sampled request's tokens one by one,
and one chip has no exchange between chips to leave out.)  Everything
of a run but the look for a chip is driven, through ``run.run_cell``."""
import pytest

from bench import run
from bench.tests import tiny

SEEDS = (2**31 + 11, 3)


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_control_fails_where_sound_runs_pass(cell):
    mode, traffic = tiny.CELLS[cell]
    m, t = tiny.config(mode), tiny.traffic(traffic)
    lim = tiny.limits(cell)
    for seed in SEEDS:
        r = run.run_cell(m, t, lim, seed, 0, control=True,
                         close_after=tiny.CLOSE_AFTER)
        c = r["check"]
        assert r["correct"], (r["compared"], c)
        control_fails = [k for k in lim["compare"]
                         if c[f"control_{k}"] > lim["compare"][k]]
        assert control_fails, c


def _token_altered(srv):
    inner, V = srv._decode, srv.cfg.vocab

    class Decode:
        def react(self):
            return inner.react()

        def __call__(self, params, state, res_vecs=None):
            new, logits, tel = inner(params, state, res_vecs)
            return dict(new, tokens=(new["tokens"] + 1) % V), logits, tel

    srv._decode = Decode()


def _state_unchanged(srv):
    inner = srv._decode

    class Decode:
        def react(self):
            return inner.react()

        def __call__(self, params, state, res_vecs=None):
            new, logits, tel = inner(params, state, res_vecs)
            return dict(new, caches=state["caches"]), logits, tel

    srv._decode = Decode()


def _prefill_token_altered(srv):
    inner, V = srv._prefill, srv.cfg.vocab

    def prefill(*a):
        tok, caches, logits = inner(*a)
        return (tok + 1) % V, caches, logits

    srv._prefill = prefill


DECODE_FAULTS = (_token_altered, _state_unchanged)
FAULTS = [(c, f) for c in ("mixtral-host-decode", "mixtral-hbm-chat")
          for f in DECODE_FAULTS] + \
    [(c, _prefill_token_altered) for c in sorted(tiny.CELLS)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__.strip('_')}"
                              for c, f in FAULTS])
def test_broken_timed_path_is_not_correct(cell, fault):
    mode, traffic = tiny.CELLS[cell]
    m, t = tiny.config(mode), tiny.traffic(traffic)
    lim = tiny.limits(cell)
    r = run.run_cell(m, t, lim, SEEDS[0], 0, fault=fault,
                     close_after=tiny.CLOSE_AFTER)
    assert not r["correct"], r["check"]
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in r["compared"].values())
