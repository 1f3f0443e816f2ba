"""Layer: device.  Seconds, among the ten longest idle gaps of the
traced stretch, in gaps no program span overlapped: ``tracereduce.py``
labels those "after <span>, before <span>".  (A label that is a span's
name says what the host was doing while the chip sat idle.)"""


def read(run):
    tr = run["trace"]
    if not tr or tr.get("idle_gaps") is None:
        return None
    return sum(s for label, s in tr["idle_gaps"] if label.startswith("after "))
