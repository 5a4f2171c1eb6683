def read(rec):
    return rec.mean("ea_rounds")
