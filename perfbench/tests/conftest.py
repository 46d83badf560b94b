import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load(name: str) -> dict:
    return json.loads((DATA / f"{name}.json").read_text())

