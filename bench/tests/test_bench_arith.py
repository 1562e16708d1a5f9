"""The benchmark's arithmetic, on the CPU: rates, percentiles,
roofline bytes and shares, and the reference's comparisons."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import measure  # noqa: E402
import reference as ref  # noqa: E402
import roofline  # noqa: E402


def test_rate_and_window():
    assert measure.rate(300, 10.0) == 30.0
    assert measure.rate(5, 0) is None
    mask = measure.in_window([0.5, 1.0, 2.0, 3.5], 1.0, 3.0)
    assert mask.tolist() == [False, True, True, False]


@pytest.mark.parametrize("q,want", [(50, 50.5), (95, 95.05), (0, 1.0),
                                    (100, 100.0)])
def test_percentile_interpolates_between_ranks(q, want):
    assert measure.percentile(range(1, 101), q) == pytest.approx(want)


def test_percentile_of_nothing_is_none():
    assert measure.percentile([], 95) is None


def test_bytes_count_only_the_words_moved():
    # 4-byte words, each read and written once: no addresses, rows or
    # padding, so a 262,144-word gather must move 2 MiB
    assert roofline.gather_bytes(262_144) == 2 * 4 * 262_144
    assert roofline.commit_bytes(2) == 16


def test_roofline_share_against_v5e_hbm():
    kind = "TPU v5 lite"
    # 819e9 bytes in one second is the whole roofline
    assert roofline.roofline_share(819e9, 1.0, kind) == pytest.approx(100)
    assert roofline.roofline_share(819e9, 4.0, kind) == pytest.approx(25)
    assert roofline.roofline_share(0, 1.0, kind) is None
    assert roofline.roofline_share(10, 0.0, kind) is None


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


# -- the reference ----------------------------------------------------------


def _bank(n=64, seed=5):
    return ref.initial_balances(seed, n, 1000, 2000)


def test_initial_balances_follow_the_seed():
    a = ref.initial_balances(2**31 + 7, 1000, 1000, 100_000)
    b = ref.initial_balances(2**31 + 7, 1000, 1000, 100_000)
    c = ref.initial_balances(2**31 + 8, 1000, 1000, 100_000)
    assert (a == b).all() and not (a == c).all()
    assert a.min() >= 1000 and a.max() < 100_000


def test_final_state_applies_each_transfer_once():
    init = _bank()
    src, dst = np.array([1, 2, 1]), np.array([3, 1, 4])
    want = init.copy()
    want[1] += -5 + 5 - 5
    want[2] -= 5
    want[3] += 5
    want[4] += 5
    assert (ref.expected_final(init, src, dst, 5) == want).all()
    assert ref.final_bad_accounts(want, init, src, dst, 5) == 0
    lost = ref.expected_final(init, src[:2], dst[:2], 5)  # 1 -> 4 lost
    assert ref.final_bad_accounts(lost, init, src, dst, 5) == 2


def test_audit_of_a_consistent_state_passes():
    init = _bank()
    src, dst = np.array([1, 7, 9]), np.array([2, 8, 10])
    none = np.zeros(3, bool)
    seen = ref.expected_final(init, src[:2], dst[:2], 5)   # a subset
    assert ref.audit_bad_accounts(seen, init, src, dst, none, 5) == 0
    assert ref.audit_bad_accounts(init, init, src, dst, none, 5) == 0
    # the first returned before the audit began: it has to be seen
    first = np.array([True, False, False])
    assert ref.audit_bad_accounts(seen, init, src, dst, first, 5) == 0


@pytest.mark.parametrize("fault", ["torn", "garbage", "double", "unknown",
                                   "stale"])
def test_audit_of_an_inconsistent_state_fails(fault):
    init = _bank()
    src, dst = np.array([1, 7]), np.array([2, 8])
    must = np.zeros(2, bool)
    seen = ref.expected_final(init, src, dst, 5)
    if fault == "torn":                       # debit seen, credit not
        seen[2] -= 5
    elif fault == "garbage":
        seen[30] += 1
    elif fault == "double":                   # one transfer applied twice
        seen[1] -= 5
        seen[2] += 5
    elif fault == "unknown":                  # money from nowhere
        seen[40] += 5
    else:                                     # a transfer that returned
        must[1] = True                        # before the audit, missed
        seen = ref.expected_final(init, src[:1], dst[:1], 5)
    assert ref.audit_bad_accounts(seen, init, src, dst, must, 5) > 0


def test_reference_bank_and_its_write_behind_control():
    init = _bank()

    def transfer(tx, i=3, j=4):
        a, b = tx.read(i), tx.read(j)
        tx.write(i, a - 5)
        tx.write(j, b + 5)

    sound = ref.ReferenceBank(init)
    sound.run(transfer, tid=0)
    assert ref.final_bad_accounts(sound.read_all(), init, [3], [4], 5) == 0
    control = ref.ReferenceBank(init, write_behind=True)
    control.run(transfer, tid=0)
    assert ref.final_bad_accounts(control.read_all(), init, [3], [4],
                                  5) == 2
    control.run(lambda tx: None, tid=0)      # the next call applies it
    assert ref.final_bad_accounts(control.read_all(), init, [3], [4],
                                  5) == 0
