"""What the wide-join templates (q04, q07, q08, q12, q19) share beside
`_grouped`: the calendar year of a date column and the keys of nations and
regions by name. numpy only; nothing of the program."""

import numpy as np

from benchmark import population

NATION_NAMES = sorted(n for n, _ in population.NATIONS)


def year(days: np.ndarray) -> np.ndarray:
    """`extract(year FROM d)` of a date column (days since 1970)."""
    return np.asarray(days).astype("datetime64[D]").astype("datetime64[Y]").astype(np.int64) + 1970


def nation_key(host: dict, name: str) -> int:
    """n_nationkey of the nation called `name`."""
    nation = host["nation"]
    return int(nation["n_nationkey"][nation["n_name"] == NATION_NAMES.index(name)][0])


def region_of(name: str) -> str:
    """The region of the nation called `name`."""
    return population.REGIONS[dict(population.NATIONS)[name]]


def nations_of_region(host: dict, region: str) -> np.ndarray:
    """n_nationkey of every nation of `region`."""
    nation, regions = host["nation"], host["region"]
    key = regions["r_regionkey"][regions["r_name"] == population.REGIONS.index(region)][0]
    return nation["n_nationkey"][nation["n_regionkey"] == key]
