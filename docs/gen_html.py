#!/usr/bin/env python
"""Build the HTML docs (reference `python_doc; make html` analog,
Makefile:46) from the repo's markdown into docs/_html/."""

import html
import os

STYLE = ("body{max-width:54em;margin:2em auto;font-family:sans-serif;"
         "line-height:1.5;padding:0 1em}pre,code{background:#f4f4f4}"
         "pre{padding:.8em;overflow-x:auto}table{border-collapse:collapse}"
         "td,th{border:1px solid #ccc;padding:.3em .6em}")

PAGES = {
    "index.html": "../README.md",
    "parity.html": "../PARITY.md",
    "survey.html": "../SURVEY.md",
    "architecture.html": "architecture.md",
    "llm_layers.html": "llm_layers.md",
    "benchmarks.html": "benchmarks.md",
    "migration.html": "migration.md",
    "tuning.html": "tuning.md",
    "deploy.html": "deploy.md",
}


def render(md_text: str) -> str:
    try:
        import markdown
        return markdown.markdown(md_text,
                                 extensions=["tables", "fenced_code"])
    except ImportError:
        return "<pre>" + html.escape(md_text) + "</pre>"


def main() -> None:
    here = os.path.dirname(os.path.abspath(__file__))
    out = os.path.join(here, "_html")
    os.makedirs(out, exist_ok=True)
    nav = " | ".join(f'<a href="{p}">{p[:-5]}</a>' for p in PAGES)
    for page, src in PAGES.items():
        path = os.path.join(here, src)
        if not os.path.exists(path):
            continue
        body = render(open(path).read())
        with open(os.path.join(out, page), "w") as f:
            f.write(f"<!doctype html><meta charset='utf-8'>"
                    f"<style>{STYLE}</style><nav>{nav}</nav>{body}")
        print("wrote", os.path.join(out, page))


if __name__ == "__main__":
    main()
