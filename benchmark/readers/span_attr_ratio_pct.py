"""One attribute of the program's spans over another, both summed over
the spans of a name in the traced window, in percent: rows attention read
over rows cached, choices that fell on the experts held over all choices.
The program writes the attributes on the span (``mpit_tpu.obs``); None
where no span of the name carries both (a program that lacks them, an
untraced run) or the whole is 0."""


def read(ctx, span: str, part: str, whole: str):
    got = [attrs for name, _a, _b, attrs in ctx["run"].get("host_spans", ())
           if name == span and part in attrs and whole in attrs]
    total = sum(a[whole] for a in got)
    return 100.0 * sum(a[part] for a in got) / total if total else None
