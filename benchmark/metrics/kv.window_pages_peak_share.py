"""Most pages of the window class that held a key some live slot's next query reads, at the end of any decode call
(the decode program's counter ``window_pages_live_peak``), over the class's pages. None on a program without a window
class."""


def read(obs):
    peak = (obs.get("stats_after") or {}).get("window_pages_live_peak")
    classes = (obs.get("kv_after") or {}).get("window_classes")
    if peak is None or not classes:
        return None
    return 100.0 * peak / sum(c["pages_total"] for c in classes)
