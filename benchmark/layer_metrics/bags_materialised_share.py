"""Share of the Check rows the native front took that were made into
a python bag: the program's `mixer_front_bags_materialised_total`
(api/take.TakenRows makes a LazyWireBag only for a row that something
on the host asks for: a host action, a quota, a row the host decides,
a response whose bytes depend on its bag, an exemplar or canary tap;
under an APA every row) over `mixer_grpc_check_requests`, the rows the
front decoded, both since the window opened. It says how often the
batch-as-one-buffer path engages: near 0 where the device decides the
rows, 100 where every row is asked for. Also prints one progress line
with the rows, the bags, the responses the front sent
(`mixer_grpc_check_responses`) and the end-to-end histogram's count
(`mixer_check_e2e_seconds_count`: one observation a batch since the
counter exists, and still a row a request) over the same window: the
three move together, less the rows in flight at the window's edges, or
work was dropped. A program without the counter reads nothing."""
import json

from istio_tpu.runtime import monitor


def _counters():
    counters = getattr(monitor, "front_bag_counters", None)
    if counters is None:
        return None
    return counters() | {
        "responses": int(monitor.CHECK_RESPONSES._value.get()),
        "e2e": monitor.CHECK_E2E_SECONDS.state()[2]}


def begin(ctx):
    return _counters()


def read(ctx, base):
    if base is None:
        return None
    moved = {k: n - base[k] for k, n in _counters().items()}
    if not moved["rows"]:
        return None
    print(json.dumps({"phase": "front_rows", "rows": moved["rows"],
                      "bags": moved["materialised"],
                      "responses": moved["responses"],
                      "e2e_count": moved["e2e"]}), flush=True)
    return 100.0 * moved["materialised"] / moved["rows"]
