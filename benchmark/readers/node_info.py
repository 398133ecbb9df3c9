"""One number of the nodes' validator-info `Node_info`, from the dumps
read just before the window's first request (`reports_before`): the
median over the nodes whose dump holds it. spec: {"field":
"Genesis_load", "key": "seconds"} reads what each node's genesis load
took as it started (plenum_tpu/server/node.py Node._load_genesis). A
node restarted from its stores carries no such field, and a program
older than the field none on any node: no dump holding it is nothing
to read."""
import statistics


def read(spec, run):
    values = []
    for report in (run.get("reports_before") or {}).values():
        value = (report.get(spec["field"]) or {}).get(spec["key"])
        if value is not None:
            values.append(value)
    return statistics.median(values) if values else None
