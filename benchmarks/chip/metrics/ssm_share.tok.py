"""Per cent of the decode program's op time in the traced span under the
``ssm`` scope: the Mamba-2 mixers between their projections (closed-loop
generation cells of hybrid models)."""

def read(ctx):
    tr = ctx.get("trace")
    if not tr or "scopes" not in tr:
        return None
    total, ssm = sum(tr["scopes"].values()), tr["scopes"].get("ssm", 0.0)
    if total <= 0 or ssm <= 0:
        return None
    return 100.0 * ssm / total
