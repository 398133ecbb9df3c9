"""How many different authors the window's confirmed writes had: the
distinct identifiers among the valid operations confirmed inside the
window. From the harness's own operations; nothing is read from the
program. A traffic mix with one author reads 1."""


def read(spec, run):
    authors = {op.request["identifier"] for op in run["released"]
               if op.valid and op.done is not None
               and op.done <= run["t1"]}
    return len(authors) or None
