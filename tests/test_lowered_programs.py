"""The programs of the serving cells lower to the text they had before
the gated-DeltaNet family came (``tests/fixtures/lowered_programs.json``,
written by ``tests/lowered_texts.py`` in a checkout of that commit).

That family brought a state carried between the chunks of a prompt
(``serving/paged_kv.py``: a later chunk's prefill reads the slot's state
row, the step leaves the rows of slots it is not given as occupied), a
prefill attention that takes the history a key block at a time over a
window longer than ``ops/paged_attention.py:DENSE_WINDOW``, an online
softmax shared with ``models/mla.py``, and the delta rule's forms moved
into ``models/delta_rule.py``.  The GPT-2, SDAR, Kimi and MiniMax
programs (a step and two prefill buckets a cell, at the rehearsal's
sizes) are equal to the last character.  Ling's, a decoder with state,
differ by the two state-row forms alone: with the parent's put back (the
step writing every row, a prefill starting from zero) they are equal
too, and with this tree's they differ."""
import json
import os

import pytest

from lowered_texts import CELLS, digests
from mxnet_tpu.serving.paged_kv import _PrefillView, _StepView

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LING = "serve_batch_ling"


def _every_row(self, name, value):
    """The parent's step: every row takes the step's state."""
    self._state[name] = value.astype(self._state[name].dtype)


def _from_zero(self, name):
    """The parent's prefill: the state starts at zero."""
    import jax.numpy as jnp

    s = self._state[name]
    return jnp.zeros(s.shape[1:], s.dtype)


@pytest.fixture(scope="module")
def before():
    with open(os.path.join(ROOT, "tests", "fixtures",
                           "lowered_programs.json")) as f:
        return json.load(f)


def _mine(before, cell):
    mine = sorted(k for k in before if k.startswith(cell + "."))
    assert len(mine) == 3
    return mine


@pytest.mark.parametrize("cell", CELLS)
def test_a_cells_programs_lower_as_before(before, cell, monkeypatch):
    if cell == LING:
        monkeypatch.setattr(_StepView, "set_state", _every_row)
        monkeypatch.setattr(_PrefillView, "state", _from_zero)
    now = digests(ROOT, cells=(cell,))
    for program in _mine(before, cell):
        assert now[program] == before[program], program


def test_lings_programs_take_the_state_row_forms(before):
    """Without the parent's forms each of Ling's three programs
    differs: the step selects the occupied rows, a prefill reads the
    slot's row where ``hist`` > 0."""
    now = digests(ROOT, cells=(LING,))
    for program in _mine(before, LING):
        assert now[program] != before[program], program
