"""setup_s (s): process start to the first scheduled arrival: loading,
the offline λ-database, the request pool, compiling and warming up."""


def read(rec):
    return rec["setup_s"]
