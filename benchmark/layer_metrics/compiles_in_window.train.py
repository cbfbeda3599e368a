"""Program: JitCache trace counts gained over the window; must read 0."""


def read(facts):
    return facts.get("compiles_in_window")
