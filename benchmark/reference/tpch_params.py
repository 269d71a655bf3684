"""TPC-H rev 2.18's substitution parameters of q1, q3 and q6, as qgen
draws them. Standard library only; imports nothing of the program.

    q1  cl. 2.4.1.3  :1 DELTA, a whole number of days in [60, 120]
    q3  cl. 2.4.3.3  :1 SEGMENT, one of the five of cl. 4.2.2.13
                     :2 DATE, a day in [1995-03-01, 1995-03-31]
    q6  cl. 2.4.6.3  :1 DATE, January 1 of a year in [1993, 1997]
                     :2 DISCOUNT in [0.02, 0.09], in steps of 0.01
                     :3 QUANTITY in [24, 25]

A parameter set is a tuple in placeholder order, of the values as the
SQL spells them (a date as ``YYYY-MM-DD``, a discount as its decimal
text, so the reference can take it in whole cents). The templates,
with qgen's placeholders ``:1``, ``:2``, ..., are
``traffic/queries/<TEMPLATES_DIR>/<class>.sql``; at the validation
parameters each substitutes to the flat ``traffic/queries/<class>.sql``
byte for byte.
"""

import datetime
import re

TEMPLATES_DIR = "tpch_qgen"
SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD")
_Q3_FIRST_DAY = datetime.date(1995, 3, 1)
_PLACEHOLDER = re.compile(r":(\d+)")

_VALIDATION = {"q1": (90,),
               "q3": ("BUILDING", "1995-03-15"),
               "q6": ("1994-01-01", "0.06", 24)}


def validation(cls: str) -> tuple:
    """The validation parameters of ``cls`` (the clause's last line)."""
    return _VALIDATION[cls]


def draw(cls: str, rng) -> tuple:
    """One parameter set of ``cls``, uniform over the clause's domain,
    from ``rng`` (a ``random.Random``)."""
    if cls == "q1":
        return (rng.randint(60, 120),)
    if cls == "q3":
        day = _Q3_FIRST_DAY + datetime.timedelta(days=rng.randint(0, 30))
        return (rng.choice(SEGMENTS), day.isoformat())
    if cls == "q6":
        return (f"{rng.randint(1993, 1997)}-01-01",
                f"0.{rng.randint(2, 9):02d}", rng.randint(24, 25))
    raise KeyError(f"no substitution parameters for {cls}")


def domain(cls: str) -> list:
    """Every parameter set of ``cls``: 61 of q1, 155 of q3, 80 of q6."""
    if cls == "q1":
        return [(d,) for d in range(60, 121)]
    if cls == "q3":
        return [(s, (_Q3_FIRST_DAY + datetime.timedelta(days=d)).isoformat())
                for s in SEGMENTS for d in range(31)]
    if cls == "q6":
        return [(f"{y}-01-01", f"0.{c:02d}", q) for y in range(1993, 1998)
                for c in range(2, 10) for q in (24, 25)]
    raise KeyError(f"no substitution parameters for {cls}")


def substitute(template: str, params) -> str:
    """``template`` with each ``:n`` replaced by the n-th parameter. Every
    parameter has to be used, and no placeholder may be left over."""
    used = {int(n) for n in _PLACEHOLDER.findall(template)}
    if used != set(range(1, len(params) + 1)):
        raise ValueError(f"placeholders {sorted(used)} for "
                         f"{len(params)} parameters")
    return _PLACEHOLDER.sub(lambda m: str(params[int(m.group(1)) - 1]),
                            template)
