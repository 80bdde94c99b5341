"""The one traffic generator: a mix's data file + a seed -> what each closed
stream sends.

A mix (traffic/<name>.json) gives the number of streams, the templates of
one pass, whether each stream permutes the pass by seed, where bindings come
from ("validation": the template's own validation values, the same every
time; "drawn": uniform from the template's parameter domains, by seed), and
the largest start stagger.  A stream repeats whole passes: it begins a new
pass only while the window is open, and finishes the pass it began.  So
every seed sends the same multiset of statements per pass, in another order
— the seed never changes the work, only its order, stagger and bindings.

A template's `?` sites are affine forms of its parameters (templates/*.json,
"sites").  Each site yields the SQL literal sent and the integer the
reference takes: days since 1970-01-01 for a date, the unscaled value for a
decimal.
"""

from __future__ import annotations

import datetime
import random

EPOCH = datetime.date(1970, 1, 1)


def _affine(site: dict, params: dict) -> int:
    return int(site.get("add", 0)) + sum(
        int(c) * int(params[p]) for p, c in site.get("of", {}).items()
    )


def site_value(site: dict, params: dict) -> tuple[str, int]:
    """-> (SQL literal, reference argument) of one `?` site."""
    kind = site["kind"]
    v = _affine(site, params)
    if kind == "integer":
        return str(v), v
    if kind == "decimal":
        scale = int(site["scale"])
        sign, mag = ("-" if v < 0 else ""), abs(v)
        return f"{sign}{mag // 10 ** scale}.{mag % 10 ** scale:0{scale}d}", v
    if kind == "date_year":  # 1 January of the year
        d = datetime.date(v, 1, 1)
    elif kind == "date_days":  # a base date moved by days
        d = datetime.date.fromisoformat(site["base"]) + datetime.timedelta(days=v)
    else:
        raise ValueError(f"unknown site kind {kind!r}")
    return f"DATE '{d.isoformat()}'", (d - EPOCH).days


class Binding:
    """One set of parameter values of a template: literals and reference args."""

    def __init__(self, template: dict, params: dict):
        self.template = template["name"]
        self.params = dict(params)
        pairs = [site_value(s, params) for s in template.get("sites", [])]
        self.literals = tuple(p[0] for p in pairs)
        self.args = tuple(p[1] for p in pairs)

    @property
    def key(self) -> tuple:
        return (self.template, self.args)


def validation(template: dict) -> Binding:
    return Binding(template, template.get("validation", {}))


def warm_bindings(template: dict) -> list[Binding]:
    """What set-up warms where bindings are drawn: the validation binding,
    then the template's `warm` list in its order — the bindings found on the
    chip to overflow a capacity learned from the ones before them."""
    return [validation(template)] + [Binding(template, p) for p in template.get("warm", [])]


def draw(template: dict, rng: random.Random) -> Binding:
    params = {
        name: rng.randint(int(dom["lo"]), int(dom["hi"]))
        for name, dom in sorted(template.get("parameters", {}).items())
    }
    return Binding(template, params)


class Stream:
    """What one closed stream sends: its pass order, its stagger and an
    endless, seeded sequence of bindings per template."""

    def __init__(self, mix: dict, templates: dict, seed: int, index: int):
        rng = random.Random(f"{int(seed)}/{index}")
        self.index = index
        self.order = list(mix["pass"])
        if mix.get("order") == "permuted":
            rng.shuffle(self.order)
        self.stagger_s = rng.uniform(0.0, float(mix.get("stagger_ms", 0))) / 1e3
        self._drawn = mix.get("bindings") == "drawn"
        self._templates = templates
        self._rng = rng

    def next_binding(self, name: str) -> Binding:
        t = self._templates[name]
        return draw(t, self._rng) if self._drawn else validation(t)


def streams(mix: dict, templates: dict, seed: int) -> list[Stream]:
    return [Stream(mix, templates, seed, i) for i in range(int(mix["streams"]))]
