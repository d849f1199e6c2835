"""A run with the timed path broken underneath comes out not correct, for
each fault a cell of this benchmark can have, and so does the control: the
operand rounded to bfloat16 on its way into the program, one precision
below the configuration's float32.  (Every cell runs on one chip, so there
is no exchange between chips to leave out.)"""
import numpy as np
import pytest

from bench.tests.rehearsal import rehearse


def _unchanged_state(monkeypatch):
    from repro.core import sem
    monkeypatch.setattr(sem, "_batch_step_binary",
                        lambda meta, rows, cols, x_pad, out, T: out)


def _half_batch(monkeypatch):
    from repro.core import sem
    step = sem._batch_step_binary

    def half(meta, rows, cols, x_pad, out, T):
        return step(meta.at[meta.shape[0] // 2:, 3].set(0), rows, cols,
                    x_pad, out, T)
    monkeypatch.setattr(sem, "_batch_step_binary", half)


def _altered_answer(monkeypatch):
    from repro.runtime import session
    consume = session.MultiplyRequest.consume

    def altered(self, y):
        consume(self, y)
        self.result = np.array(self.result)
        self.result[:, -1] *= np.float32(1.001)
    monkeypatch.setattr(session.MultiplyRequest, "consume", altered)


def _bf16_operand(monkeypatch):
    import ml_dtypes
    from repro.runtime import SessionSpec
    multiply = SessionSpec.multiply.__func__

    def low(cls, x, *args, **kw):
        x = np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float32)
        return multiply(cls, x, *args, **kw)
    monkeypatch.setattr(SessionSpec, "multiply", classmethod(low))


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_answer, _bf16_operand])
@pytest.mark.parametrize("cell", ["g22-full", "g22-query"])
def test_fault_is_not_correct(fault, cell, monkeypatch, tmp_path):
    fault(monkeypatch)
    result, lines = rehearse(cell, 31, tmp_path)
    assert not result["correct"], lines
    c = result["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]
