"""The comparison that decides ``correct``, run end to end at the cells'
tiny rehearsal sizes on the CPU (the harness's look for a chip is the
only part skipped):

* the sound program is correct;
* the control (the reference search with its playouts cut short, in the
  program's place) is not;
* each fault planted under the timed path is not: a step that leaves
  its state unchanged, half of each batch left out, and the chosen move
  altered where it is produced.  (The cells run on one chip, so there is
  no exchange between chips to leave out.)
"""
import json

import jax
import jax.numpy as jnp
import pytest

import benchmarks.chip.drivers.goservice as goservice
from benchmarks.chip.harness import main

CELLS = ["serve9.serve", "fuego9.selfplay"]


def run(capsys, cell, *extra):
    argv = ["--workload", cell, "--seed", str(2 ** 31 + 17), "--seconds",
            "2", "--trace", "0", "--cpu-rehearsal", *extra]
    assert main(argv) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["rehearsal"] and line["metrics"] == {}
    return line


@pytest.fixture(autouse=True)
def short_drain(monkeypatch):
    monkeypatch.setattr(goservice, "DRAIN_S", 2.0)
    monkeypatch.setattr(goservice, "WARM_S", 5.0)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_program_is_correct(capsys, cell):
    line = run(capsys, cell)
    assert line["correct"], line["checks"]
    assert line["checks"]["ref_mismatch_share"]["value"] == 0.0


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(capsys, cell):
    line = run(capsys, cell, "--control")
    assert not line["correct"], line["checks"]


def stuck_step(monkeypatch):
    from repro.core.service import SearchService

    def advance(self, pool):
        return pool._replace(parity=pool.parity + 1,
                             occ_steps=pool.occ_steps + 1)
    monkeypatch.setattr(SearchService, "_advance", advance)


def half_batch(monkeypatch):
    """Half of each batch left out: the first half of every search batch
    is answered with the second half's results, and the second half of
    the pool's slots keep their game states instead of moving."""
    from repro.core.mcts import MCTS
    from repro.core.service import SearchService
    search, advance = MCTS.search_batch, SearchService._advance

    def halved(self, roots, rngs, sims=None, params=None):
        out = search(self, roots, rngs, sims, params)
        h = out.action.shape[0] // 2

        def copy(x):
            return x if h == 0 else jnp.concatenate([x[h:2 * h], x[h:]])
        return out._replace(action=copy(out.action),
                            root_visits=copy(out.root_visits))

    def stalled(self, pool):
        new = advance(self, pool)
        h = pool.slots.ticket.shape[0] // 2

        def keep(old, moved):
            return jnp.concatenate([moved[:h], old[h:]])
        states = jax.tree.map(keep, pool.slots.states, new.slots.states)
        return new._replace(slots=new.slots._replace(states=states))
    monkeypatch.setattr(MCTS, "search_batch", halved)
    monkeypatch.setattr(SearchService, "_advance", stalled)


def altered_move(monkeypatch):
    from repro.core import tree

    def least_visited(visits, legal):
        seen = legal & (visits > 0)
        alt = jnp.argmin(jnp.where(seen, visits, jnp.inf)).astype(jnp.int32)
        return jnp.where(seen.any(), alt, jnp.argmax(legal).astype(
            jnp.int32))
    monkeypatch.setattr(tree, "select_action", least_visited)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [stuck_step, half_batch, altered_move])
def test_planted_fault_fails(capsys, monkeypatch, cell, fault):
    fault(monkeypatch)
    line = run(capsys, cell)
    assert not line["correct"], (fault.__name__, line["checks"])
