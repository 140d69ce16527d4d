"""Vectorised pieces shared by the configurations' data generators.

Everything is plain numpy from one ``numpy.random.Generator``: no Python
loop runs over rows. Strings come back as numpy ``U`` arrays, which the
program's ``Table`` turns into its own string columns.
"""
from __future__ import annotations

import numpy as np

EPOCH = np.datetime64("1992-01-01")

NATIONS = ("ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
           "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ",
           "JAPAN", "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU",
           "CHINA", "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA",
           "UNITED KINGDOM", "UNITED STATES")
# TPC-H's nation -> region map (REGION keys 0..4)
NATION_REGION = (0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2,
                 3, 4, 2, 3, 3, 1)
REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")
PRIORITIES = ("1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW")
SHIPMODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
COLORS = ("almond", "antique", "aquamarine", "azure", "beige", "bisque",
          "black", "blanched", "blue", "blush", "brown", "burlywood",
          "burnished", "chartreuse", "chiffon", "chocolate", "coral",
          "cornflower", "cornsilk", "cream", "cyan", "dark", "deep", "dim",
          "dodger", "drab", "firebrick", "floral", "forest", "frosted",
          "gainsboro", "ghost", "goldenrod", "green", "grey", "honeydew",
          "hot", "indian", "ivory", "khaki", "lace", "lavender", "lawn",
          "lemon", "light", "lime", "linen", "magenta", "maroon", "medium",
          "metallic", "midnight", "mint", "misty", "moccasin", "navajo",
          "navy", "olive", "orange", "orchid", "pale", "papaya", "peach",
          "peru", "pink", "plum", "powder", "puff", "purple", "red", "rose",
          "rosy", "royal", "saddle", "salmon", "sandy", "seashell", "sienna",
          "sky", "slate", "smoke", "snow", "spring", "steel", "tan",
          "thistle", "tomato", "turquoise", "violet", "wheat", "white",
          "yellow")
TYPE_1 = ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
TYPE_2 = ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
TYPE_3 = ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")
CONTAINER_1 = ("SM", "LG", "MED", "JUMBO", "WRAP")
CONTAINER_2 = ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM")
_ALPHABET = np.array([ord(c) for c in
                      "abcdefghijklmnopqrstuvwxyz abcdefghijklmnopqrstuvwxyz"
                      " ,.0123456789"], dtype=np.uint32)


def _datekeys(days: np.ndarray) -> np.ndarray:
    d = EPOCH + days.astype("timedelta64[D]")
    y = d.astype("datetime64[Y]").astype(np.int64) + 1970
    m = d.astype("datetime64[M]").astype(np.int64) % 12 + 1
    dd = (d - d.astype("datetime64[M]")).astype(np.int64) + 1
    return (y * 10000 + m * 100 + dd).astype(np.int32)


def datekeys(days: np.ndarray) -> np.ndarray:
    """Days since 1992-01-01 -> int32 yyyymmdd (by table lookup: the
    calendar arithmetic runs once per distinct day, not once per row)."""
    table = _datekeys(np.arange(int(days.max()) + 1 if len(days) else 0))
    return table[days]


def exact_counts(rng: np.random.Generator, n: int, lo: int, hi: int,
                 total: int) -> np.ndarray:
    """``n`` counts in ``[lo, hi]``, uniform-ish, that add up to
    ``total`` exactly, so every seed makes the same number of rows."""
    if not n * lo <= total <= n * hi:
        raise ValueError(f"{total} rows cannot be {n} counts in "
                         f"[{lo}, {hi}]")
    counts = rng.integers(lo, hi + 1, n).astype(np.int64)
    diff = total - int(counts.sum())
    while diff:
        step = 1 if diff > 0 else -1
        room = np.flatnonzero(counts < hi if step > 0 else counts > lo)
        pick = rng.choice(room, size=min(abs(diff), len(room)),
                          replace=False)
        counts[pick] += step
        diff -= step * len(pick)
    return counts


def sparse_orderkeys(n: int) -> np.ndarray:
    """TPC-H's order keys: 8 of each 32 consecutive values are used."""
    i = np.arange(n, dtype=np.int64)
    return ((i // 8) * 32 + i % 8 + 1).astype(np.int32)


VOCABULARY = 65536


def text(rng: np.random.Generator, n: int, lo: int, hi: int) -> np.ndarray:
    """``n`` strings of ``lo`` to ``hi`` random characters, drawn from a
    vocabulary of at most ``VOCABULARY`` such strings (made from the same
    generator), so a 6M-row comment column costs no 6M x 43 draw."""
    if n > VOCABULARY:
        return text(rng, VOCABULARY, lo, hi)[
            rng.integers(0, VOCABULARY, n)]
    codes = _ALPHABET[rng.integers(0, len(_ALPHABET), (n, hi))]
    lengths = rng.integers(lo, hi + 1, n)
    codes[np.arange(hi)[None, :] >= lengths[:, None]] = 0
    # a leading/trailing space would be stripped by nobody, but keep the
    # first character a letter so no string is all blanks
    codes[:, 0] = _ALPHABET[rng.integers(0, 26, n)]
    return np.ascontiguousarray(codes).view(f"<U{hi}").reshape(n)


def pick(rng: np.random.Generator, values, n: int) -> np.ndarray:
    """``n`` draws from ``values`` (any sequence of strings)."""
    vocab = np.asarray(values)
    return vocab[rng.integers(0, len(vocab), n)]


def numbered(prefix: str, keys: np.ndarray, width: int) -> np.ndarray:
    """``prefix`` + zero-padded ``keys`` (e.g. ``Customer#000000001``)."""
    return np.char.add(prefix, np.char.zfill(keys.astype(f"U{width}"),
                                             width))


def phones(rng: np.random.Generator, nation: np.ndarray) -> np.ndarray:
    """TPC-H phone numbers: ``CC-AAA-BBB-CCCC`` with CC = nation + 10."""
    parts = [numbered("", nation.astype(np.int64) + 10, 2)]
    for lo, hi, w in ((100, 999, 3), (100, 999, 3), (1000, 9999, 4)):
        parts.append(numbered("-", rng.integers(lo, hi + 1, len(nation)),
                              w))
    out = parts[0]
    for p in parts[1:]:
        out = np.char.add(out, p)
    return out


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """TPC-H's P_RETAILPRICE in cents (90,000 to 209,900)."""
    pk = partkey.astype(np.int64)
    return (90000 + (pk // 10) % 20001 + 100 * (pk % 1000)).astype(np.int64)
