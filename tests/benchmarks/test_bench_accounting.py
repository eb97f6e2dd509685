import pytest

from benchmarks import accounting


@pytest.mark.parametrize("xs,q,want", [
    ([1.0], 0.5, 1.0), ([1, 2, 3, 4], 0.5, 2.5), ([1, 2, 3, 4], 0.0, 1.0),
    ([1, 2, 3, 4], 1.0, 4.0), ([10, 20], 0.25, 12.5),
    ([3, 1, 2], 0.5, 2.0), (list(range(101)), 0.99, 99.0)])
def test_quantile_interpolates(xs, q, want):
    assert accounting.quantile(xs, q) == pytest.approx(want)


def test_quantile_refuses_nothing_and_nonsense():
    with pytest.raises(ValueError):
        accounting.quantile([], 0.5)
    with pytest.raises(ValueError):
        accounting.quantile([1.0], 1.5)


def test_gaps_count_where_their_later_token_lands():
    times = [[1.0, 2.0, 4.0], [3.5, 3.9]]
    assert sorted(accounting.gaps_in_window(times, 2.0, 4.0)) == \
        pytest.approx([0.4, 1.0])
    assert accounting.gaps_in_window(times, 0.0, 10.0).__len__() == 3
