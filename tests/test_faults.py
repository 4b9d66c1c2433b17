"""Unit tests for the shared fault taxonomy (`runtime.faults`):
classification, deterministic-refailure poison detection, retry budget
with exponential backoff, and injected-clock deadlines."""
import pytest

from repro.runtime import fault_tolerance, faults


# ---------------------------------------------------------------------------
# Classification
# ---------------------------------------------------------------------------

def test_classify_transient_types():
    for exc in (RuntimeError("oom"), OSError("io"),
                FloatingPointError("nan")):
        assert faults.classify(exc) == faults.TRANSIENT


def test_classify_valueerror_poisons_only_on_refailure():
    """ValueError gets one retry of grace; an identical re-failure
    proves determinism and reclassifies to poison."""
    exc = ValueError("bad factor 0")
    assert faults.classify(exc, seen_before=False) == faults.TRANSIENT
    assert faults.classify(exc, seen_before=True) == faults.POISON


@pytest.mark.parametrize("message,expected", [
    ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem",
     faults.FATAL),
    ("INVALID_ARGUMENT: Buffer has been deleted or donated",
     faults.FATAL),
    ("UNAVAILABLE: TPU device lost", faults.TRANSIENT),
])
def test_classify_xla_runtime_errors_by_status(message, expected):
    """A compile refusal, an OOM or a bad argument fails again on a
    replay of the same program, so only other statuses are retried."""
    import jax
    assert faults.classify(jax.errors.JaxRuntimeError(message)) \
        == expected


def test_classify_deleted_array_is_fatal():
    import jax.numpy as jnp
    x = jnp.ones(3)
    x.delete()
    with pytest.raises(RuntimeError) as info:
        x + 1
    assert faults.classify(info.value) == faults.FATAL


def test_classify_fatal():
    for exc in (TypeError("t"), AttributeError("a"), KeyError("k")):
        assert faults.classify(exc) == faults.FATAL
    # fatal regardless of history: retrying a bug is never right
    assert faults.classify(TypeError("t"),
                           seen_before=True) == faults.FATAL


def test_fault_signature_distinguishes_type_and_message():
    assert faults.fault_signature(ValueError("x")) \
        != faults.fault_signature(ValueError("y"))
    assert faults.fault_signature(ValueError("x")) \
        != faults.fault_signature(RuntimeError("x"))


def test_fault_record_fields():
    rec = faults.fault_record(ValueError("bad"), faults.POISON,
                              retries=3)
    assert rec == {"fault_class": "poison", "type": "ValueError",
                   "message": "bad", "retries": 3}


def test_taxonomy_shared_with_fault_tolerance_driver():
    """The training driver and the serving layer literally share one
    transient tuple — the unification this module exists for."""
    assert fault_tolerance.faults.TRANSIENT_TYPES \
        is faults.TRANSIENT_TYPES
    assert ValueError not in faults.TRANSIENT_TYPES


# ---------------------------------------------------------------------------
# Retry policy / state
# ---------------------------------------------------------------------------

def test_backoff_grows_exponentially_and_caps():
    pol = faults.RetryPolicy(max_retries=10, backoff_base_s=0.1,
                             backoff_factor=2.0, backoff_max_s=0.5)
    assert pol.backoff_s(1) == pytest.approx(0.1)
    assert pol.backoff_s(2) == pytest.approx(0.2)
    assert pol.backoff_s(3) == pytest.approx(0.4)
    assert pol.backoff_s(4) == pytest.approx(0.5)   # capped
    assert pol.backoff_s(9) == pytest.approx(0.5)


def test_retry_state_transient_budget_then_give_up():
    st = faults.RetryState(faults.RetryPolicy(max_retries=2,
                                              backoff_base_s=0.1))
    a1, d1 = st.next_action(RuntimeError("oom"))
    a2, d2 = st.next_action(RuntimeError("oom"))
    a3, _ = st.next_action(RuntimeError("oom"))
    assert (a1, a2, a3) == (faults.RETRY, faults.RETRY, faults.GIVE_UP)
    assert d2 > d1 > 0
    assert st.retries == 2
    assert st.backoff_total_s == pytest.approx(d1 + d2)


def test_retry_state_poison_on_identical_refailure():
    st = faults.RetryState(faults.RetryPolicy(max_retries=5))
    assert st.next_action(ValueError("bad"))[0] == faults.RETRY
    action, delay = st.next_action(ValueError("bad"))
    assert action == faults.QUARANTINE and delay == 0.0
    assert st.last_fault["fault_class"] == faults.POISON
    assert st.retries == 1   # the poison detection spent one retry


def test_retry_state_different_valueerrors_stay_transient():
    """Distinct signatures are not 'the same failure again'."""
    st = faults.RetryState(faults.RetryPolicy(max_retries=5))
    assert st.next_action(ValueError("a"))[0] == faults.RETRY
    assert st.next_action(ValueError("b"))[0] == faults.RETRY


def test_retry_state_fatal_gives_up_immediately():
    st = faults.RetryState(faults.RetryPolicy(max_retries=5))
    action, delay = st.next_action(TypeError("bug"))
    assert action == faults.GIVE_UP and delay == 0.0
    assert st.retries == 0
    assert st.last_fault["fault_class"] == faults.FATAL


def test_shard_and_surrogate_faults_are_transient_runtime_errors():
    assert issubclass(faults.ShardLossFault, RuntimeError)
    assert issubclass(faults.SurrogateFault, RuntimeError)
    assert faults.classify(faults.ShardLossFault("gone")) \
        == faults.TRANSIENT


# ---------------------------------------------------------------------------
# Deadlines (injected clock)
# ---------------------------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_deadline_with_fake_clock():
    clk = _Clock()
    dl = faults.Deadline(clk, 5.0)
    assert not dl.expired()
    assert dl.remaining() == pytest.approx(5.0)
    clk.t += 4.0
    assert not dl.expired()
    assert dl.elapsed() == pytest.approx(4.0)
    clk.t += 1.5
    assert dl.expired()
    assert dl.remaining() == 0.0


def test_deadline_none_never_expires():
    clk = _Clock()
    dl = faults.Deadline(clk, None)
    clk.t += 1e9
    assert not dl.expired()
    assert dl.remaining() == float("inf")
