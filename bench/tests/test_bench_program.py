"""The system under test as the benchmark drives it, on the CPU at a tiny
size: a closed session frees its engine at once, and a traced session
hands the program's spans and reuse ledgers to the record."""
import gc
import weakref
from types import SimpleNamespace

import pytest

from bench.program import Server
from bench.record import Recorder
from bench.reference import qwen2
from bench.tests.test_bench_readers import reader
from bench.tests.tiny import CONFIG, TRAFFIC
from bench.traffic.generator import generate_session

CFG = dict(CONFIG, hidden_size=128, intermediate_size=256,
           num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=2,
           vocab_size=512)
TR = dict(TRAFFIC, agents=3)


@pytest.fixture
def server():
    from repro.serving.trace import clear_programs
    clear_programs()
    weights = qwen2.make_weights(7, CFG)

    def make(trace=False):
        rec = Recorder()
        return Server(CFG, TR, weights,
                      generate_session(TR, CFG["vocab_size"], 7, 0), rec, 0,
                      trace=trace), rec
    return make


def test_close_frees_the_engine_without_the_cycle_collector(server):
    srv, _ = server()
    for _ in range(2):               # a prefill round, then plan/recover/store
        srv.run_round()
    engine = weakref.ref(srv.engine)
    policy = srv.policy
    gc.disable()
    try:
        srv.close()
        assert engine() is None
    finally:
        gc.enable()
    assert "plan" not in vars(policy) and policy.plan.__self__ is policy


def test_a_traced_session_records_the_programs_spans_and_ledgers(server):
    srv, rec = server(trace=True)
    srv.run_round()
    names = {s.name for s in rec.program_spans}
    assert {"round", "recover", "decode", "store", "jit:prefill"} <= names
    assert rec.rounds[0].reuse["jit"]["new_programs"]["prefill"] == 1
    srv.run_round()
    assert rec.rounds[1].reuse["bucket"]["S"] == rec.batches[1].prompt_len
    view = SimpleNamespace(rec=rec, window=(rec.rounds[0].t0,
                                            rec.rounds[-1].t1),
                           window_rounds=lambda: rec.rounds)
    assert reader("jit.new_programs_per_round")(view) >= 1.0
    assert reader("jit.first_call_s_per_round")(view) > 0.0
    srv.close()


def test_an_untraced_session_records_no_program_spans(server):
    srv, rec = server()
    srv.run_round()
    assert srv.tracer is None and rec.program_spans == []
    assert "jit" in rec.rounds[0].reuse
    srv.close()
